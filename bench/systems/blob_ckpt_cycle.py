"""Checkpoint cycle: one ``BlobCheckpointer`` on one ``Cluster`` saving and
restoring this chip's shard of a training state.

Set-up makes the shard on the device from the seed (float32 params and
AdamW m and v, each leaf cut along a dimension the shard count divides),
then saves and restores it once, so that the blob exists and every
transfer program is compiled.

The window loops back to back until ``--seconds`` have passed, finishing
the cycle it is in: an untimed step on the device that moves every element
of the state (the save hashes pages and writes only dirty ones, so an
unchanged state would measure nothing), a timed ``save`` (the call a
training loop blocks in), then a timed ``restore`` onto the chip until
every leaf is ready. After each restore, an untimed comparison on the
device counts the elements whose bits differ from the state that was saved
(limit: 0).

The control (``control=True``) puts the reference's lower precision in the
program's place: each window save is given the state rounded through
bfloat16, and each restore is compared with the state as it was, under the
same name and limit, so a sound control run comes out not correct.
"""

from __future__ import annotations

import dataclasses
import sys
import time
from typing import List

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from bench import harness
from repro.core import Cluster
from repro.storage.checkpoint import BlobCheckpointer

HOST_SPANS = ("ckpt_save", "restore", "store_write", "store_read")


def log(msg: str) -> None:
    print(f"[blob_ckpt_cycle] {msg}", file=sys.stderr, flush=True)


def leaf_shapes(cfg: dict) -> dict:
    """Full (unsharded) parameter shapes, per-layer weights stacked."""
    L, d, F = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["intermediate_size"]
    H, K, D, V = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"], cfg["vocab_size"])
    shapes = {
        "embed": (V, d),
        "layers": {
            "ln1": (L, d), "wq": (L, d, H, D), "wk": (L, d, K, D), "wv": (L, d, K, D),
            "wo": (L, H, D, d), "ln2": (L, d), "wi": (L, d, F), "wg": (L, d, F),
            "w_down": (L, F, d),
        },
        "ln_f": (d,),
    }
    if not cfg["tie_word_embeddings"]:
        shapes["lm_head"] = (V, d)
    return shapes


def shard_shape(shape: tuple, ways: int) -> tuple:
    """Cut the largest dimension that ``ways`` divides."""
    fits = [i for i, n in enumerate(shape) if n % ways == 0]
    if not fits:
        raise ValueError(f"no dimension of {shape} divides into {ways}")
    i = max(fits, key=lambda j: shape[j])
    return shape[:i] + (shape[i] // ways,) + shape[i + 1:]


def is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(n, int) for n in x)


@dataclasses.dataclass
class Sut:
    ckpt: BlobCheckpointer
    state: dict
    step: object
    differing: object
    device: jax.Device
    n_bytes: int
    ref: object
    control: bool
    lower: object = None  # the control's rounding, compiled in set-up
    key: object = None
    step_no: int = 0


def setup(cell: harness.Cell, seed: int, seconds: float, rate: float = 0.0,
          control: bool = False) -> Sut:
    cfg = cell.config
    ways = int(cfg["fsdp_ways"])
    dtype = jnp.dtype(cfg["state_dtype"])
    shapes = jax.tree.map(lambda s: shard_shape(s, ways), leaf_shapes(cfg), is_leaf=is_shape)
    dev = jax.local_devices()[0]

    @jax.jit
    def make(key):
        leaves, treedef = jax.tree.flatten(shapes, is_leaf=is_shape)
        keys = jax.random.split(key, 3 * len(leaves))
        p = [0.02 * jax.random.normal(keys[i], s, dtype) for i, s in enumerate(leaves)]
        m = [1e-3 * jax.random.normal(keys[len(leaves) + i], s, dtype) for i, s in enumerate(leaves)]
        v = [1e-6 * jax.random.uniform(keys[2 * len(leaves) + i], s, dtype) for i, s in enumerate(leaves)]
        tree = lambda xs: jax.tree.unflatten(treedef, xs)
        return {"params": tree(p), "opt": {"m": tree(m), "v": tree(v),
                                           "step": jnp.zeros((), jnp.int32)}}

    def step(state, key):
        """An AdamW-shaped update with a random gradient: every element moves."""
        leaves, treedef = jax.tree.flatten(state["params"])
        keys = jax.random.split(key, len(leaves))
        g = [jax.random.normal(k, x.shape, x.dtype) for k, x in zip(keys, leaves)]
        m = [0.9 * a + 0.1 * b for a, b in zip(jax.tree.leaves(state["opt"]["m"]), g)]
        v = [0.999 * a + 0.001 * b * b for a, b in zip(jax.tree.leaves(state["opt"]["v"]), g)]
        p = [x - 1e-4 * a / (jnp.sqrt(b) + 1e-8) for x, a, b in zip(leaves, m, v)]
        tree = lambda xs: jax.tree.unflatten(treedef, xs)
        return {"params": tree(p), "opt": {"m": tree(m), "v": tree(v),
                                           "step": state["opt"]["step"] + 1}}

    step = jax.jit(step, donate_argnums=0)

    t0 = time.perf_counter()
    state = make(harness.device_key(seed))
    jax.block_until_ready(state)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
    log(f"state of {n_bytes} bytes ({len(jax.tree.leaves(state))} leaves) made in "
        f"{time.perf_counter() - t0:.2f} s")
    cluster = Cluster(**cfg["store"])
    ck = cfg["checkpointer"]
    ckpt = BlobCheckpointer(cluster.session(), state, page_size=int(ck["page_size"]),
                            keep_last=int(ck["keep_last"]))
    ref = harness.reference(cfg["reference"])
    sut = Sut(ckpt, state, step, jax.jit(ref.differing_elements), dev, n_bytes, ref, control)
    t0 = time.perf_counter()
    sut.state = sut.step(sut.state, jax.random.fold_in(harness.device_key(seed), 0))
    ckpt.save(0, sut.state)
    restored = ckpt.restore(shardings=SingleDeviceSharding(dev))
    jax.block_until_ready(restored)
    bad = int(sut.differing(restored, sut.state))
    if bad:
        raise RuntimeError(f"set-up restore differs from the saved state in {bad} elements")
    del restored
    if control:
        sut.lower = jax.jit(ref.lower_precision)
        jax.block_until_ready(sut.lower(sut.state))
    log(f"warm save and restore in {time.perf_counter() - t0:.2f} s")
    sut.key = harness.device_key(seed)
    return sut


def instrument(sut: Sut, spans: harness.Spans) -> List[str]:
    """Spans around the store calls the checkpointer makes (traced runs)."""
    spans.wrap(sut.ckpt.handle, "write", "store_write")
    spans.wrap(sut.ckpt.handle, "read", "store_read")
    return list(HOST_SPANS)


def drive(sut: Sut, seconds: float, spans: harness.Spans) -> dict:
    sharding = SingleDeviceSharding(sut.device)
    counts = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        sut.step_no += 1
        sut.state = sut.step(sut.state, jax.random.fold_in(sut.key, sut.step_no))
        saved = sut.lower(sut.state) if sut.control else sut.state
        jax.block_until_ready(saved)
        with spans.span("ckpt_save"):
            sut.ckpt.save(sut.step_no, saved)
        del saved
        with spans.span("restore"):
            restored = sut.ckpt.restore(shardings=sharding)
            jax.block_until_ready(restored)
        counts.append(sut.differing(restored, sut.state))
        del restored
    saves = [b - a for _, a, b, _ in spans.of("ckpt_save")]
    restores = [b - a for _, a, b, _ in spans.of("restore")]
    log(f"window: {len(saves)} saves and restores of {sut.n_bytes} bytes in "
        f"{time.perf_counter() - t0:.2f} s")
    return {"attempted": len(saves) + len(restores), "failed": 0,
            "save_s": saves, "restore_s": restores,
            "differing": int(sum(int(c) for c in counts))}


def check(sut: Sut, window: dict) -> List[dict]:
    return [{"name": "restored_elements_differing", "value": window["differing"], "limit": 0}]
