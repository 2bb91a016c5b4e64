"""Training step factory: loss → grads (with optional microbatch gradient
accumulation) → AdamW, all as a single jit-able function."""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ModelConfig
from repro.models.lm import Model
from repro.parallel import sharding as shd
from repro.parallel.axisinfo import AxisInfo
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update


def make_train_step(model: Model, cfg: ModelConfig, axis_info: Optional[AxisInfo],
                    opt_cfg: AdamWConfig, param_shardings=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics).

    ``cfg.grad_accum > 1`` splits the batch into microbatches scanned
    sequentially, accumulating fp32 gradients — trades step latency for
    activation memory (the standard large-model fit knob).

    ``param_shardings``: NamedSharding tree for the params. When given, each
    microbatch's gradients are constrained to it INSIDE the accumulation scan,
    so GSPMD reduce-scatters the per-microbatch grads (sharded like the
    params) instead of all-reducing the full gradient tree every microbatch —
    a ~(n_data−1)× collective-byte saving (EXPERIMENTS.md §Perf).
    """

    def loss_fn(params, batch):
        loss, metrics = model.train_loss(params, batch, axis_info)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def shard_grads(grads):
        if param_shardings is None:
            return grads
        return jax.tree.map(jax.lax.with_sharding_constraint, grads, param_shardings)

    n_batch_shards = 1
    if axis_info is not None:
        for a in axis_info.batch_axes:
            n_batch_shards *= axis_info.mesh.shape[a]

    def train_step(params, opt_state, batch):
        B0 = jax.tree.leaves(batch)[0].shape[0]
        # keep every microbatch divisible by the DP shard count; if the batch
        # itself is smaller than the shard count (tiny elastic runs), fall
        # back to A=1 with replicated batches
        A = max(1, min(cfg.grad_accum, B0 // max(n_batch_shards, 1)))
        while A > 1 and (B0 % A or (B0 // A) % n_batch_shards):
            A -= 1
        if A <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
            grads = shard_grads(jax.tree.map(lambda g: g.astype(jnp.float32), grads))
        else:
            B = jax.tree.leaves(batch)[0].shape[0]
            micro = jax.tree.map(lambda x: x.reshape(A, B // A, *x.shape[1:]), batch)
            mb0 = jax.tree.map(lambda x: x[0], micro)
            (loss0, metrics0), g0 = grad_fn(params, mb0)
            g0 = shard_grads(jax.tree.map(lambda g: g.astype(jnp.float32), g0))

            def body(carry, mb):
                g_acc, loss_acc, metrics_acc = carry
                (l, m), g = grad_fn(params, mb)
                g = shard_grads(jax.tree.map(lambda gg: gg.astype(jnp.float32), g))
                g_acc = jax.tree.map(lambda a, gg: a + gg, g_acc, g)
                metrics_acc = jax.tree.map(lambda a, mm: a + mm, metrics_acc, m)
                return (g_acc, loss_acc + l, metrics_acc), None

            rest = jax.tree.map(lambda x: x[1:], micro)
            (g_sum, loss_sum, metrics_sum), _ = lax.scan(
                body, (g0, loss0, metrics0), rest
            )
            grads = jax.tree.map(lambda g: g / A, g_sum)
            loss = loss_sum / A
            metrics = jax.tree.map(lambda m: m / A, metrics_sum)

        params, opt_state, opt_metrics = adamw_update(params, grads, opt_state, opt_cfg)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return params, opt_state, metrics

    return train_step


def init_train_state(model: Model, cfg: ModelConfig, key,
                     axis_info: Optional[AxisInfo]) -> Tuple[Any, Any, Any]:
    """``(params, opt_state, shardings)``, made by one jitted program that
    writes every array straight into its final layout: sharded over the mesh
    when there is one, so no device ever holds the whole state.
    ``shardings`` is ``(param_shardings, opt_shardings)``, or ``None``
    without a mesh."""
    axes = {}

    def init(k):
        params, axes["tree"] = model.init(k)
        return params, adamw_init(params)

    if axis_info is None:
        return (*jax.jit(init)(key), None)
    params_shape, _ = jax.eval_shape(init, key)
    p_shard = shd.param_shardings(params_shape, axes["tree"], cfg, axis_info)
    o_shard = {"m": p_shard, "v": p_shard, "step": None}
    params, opt_state = jax.jit(init, out_shardings=(p_shard, o_shard))(key)
    return params, opt_state, (p_shard, o_shard)
