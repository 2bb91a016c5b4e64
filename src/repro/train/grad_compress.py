"""Int8 error-feedback gradient compression for the cross-pod all-reduce.

Within a pod, gradients reduce over fast ICI; across pods they cross the slow
DCN link. This module compresses exactly that hop: per-pod-reduced gradients
are quantized to int8 with a per-tensor scale, all-reduced over the ``pod``
axis in int32, and dequantized — a ~4× wire saving on the slowest link. The
quantization error is carried in an error-feedback accumulator (EF-SGD), so
the bias vanishes over steps instead of accumulating.

Runs under ``shard_map`` over the full mesh: each leaf keeps its own
data/model PartitionSpec (passed in), and only the unmentioned ``pod`` axis is
reduced — so no resharding of the (possibly FSDP/TP-sharded) gradients is ever
triggered.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.axisinfo import AxisInfo


def ef_init(grads_shape) -> Any:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads_shape)


def _compress_one(g: jnp.ndarray, err: jnp.ndarray, axis: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    gf = g.astype(jnp.float32) + err
    scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
    scale = jax.lax.pmax(scale, axis)  # sync scales (scalar — negligible bytes)
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    new_err = gf - q.astype(jnp.float32) * scale
    q_sum = jax.lax.psum(q.astype(jnp.int32), axis)
    n = jax.lax.psum(1, axis)
    mean = q_sum.astype(jnp.float32) * scale / n
    return mean.astype(g.dtype), new_err


def compressed_pod_mean(grads, err_state, axis_info: AxisInfo, specs_tree, pod_axis: str = "pod"):
    """Mean-reduce ``grads`` over the pod axis with int8 EF compression.

    ``grads`` must already be identical within each pod (GSPMD's DP reduction
    guarantees this); ``specs_tree`` holds each leaf's PartitionSpec over the
    non-pod axes so nothing is resharded. Returns (grads_mean, new_err_state).
    """
    if pod_axis not in axis_info.mesh.axis_names:
        return grads, err_state  # single-pod: nothing to do

    mesh = axis_info.mesh
    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err_state)
    flat_s = jax.tree.leaves(specs_tree, is_leaf=lambda x: isinstance(x, P))

    def wrapped(*leaves):
        n = len(leaves) // 2
        outs = [_compress_one(g, e, pod_axis) for g, e in zip(leaves[:n], leaves[n:])]
        return tuple(o[0] for o in outs) + tuple(o[1] for o in outs)

    specs = tuple(flat_s) + tuple(flat_s)
    outs = jax.shard_map(
        wrapped, mesh=mesh, in_specs=specs, out_specs=specs, check_vma=False
    )(*flat_g, *flat_e)
    out_g = jax.tree.unflatten(treedef, outs[: len(flat_g)])
    out_e = jax.tree.unflatten(treedef, outs[len(flat_g) :])
    return out_g, out_e
