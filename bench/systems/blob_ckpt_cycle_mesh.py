"""Checkpoint cycle on a 2x2 mesh: one host's four ranks of the
``blob_ckpt_cycle`` deployment, each chip holding its 1/64 shard, saving
into and restoring from one blob at once.

Each leaf of the state is the one-chip cell's leaf with its cut dimension
four times as long, sharded over both mesh axes, so that chip ``i`` holds
what rank ``i`` of the 64 would: the one-chip shard's shape, its own data.
The replicated step counter is on every chip.

Set-up, the window, the checks and the control are ``blob_ckpt_cycle``'s
(see that module), with the mesh's ``NamedSharding``s where it names one
chip: an untimed step on the mesh, a timed ``save`` of the sharded state, a
timed ``restore(shardings=<the mesh's shardings>)`` until every shard is
ready, then the comparison on the devices. The window also keeps each
save's ``CheckpointRecord``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import harness
from repro.core import Cluster
from repro.storage.checkpoint import BlobCheckpointer

base = harness.load_module(harness.BENCH_DIR / "systems" / "blob_ckpt_cycle.py")
HOST_SPANS = base.HOST_SPANS + ("shard_save", "shard_restore")
AXES = ("x", "y")
check = base.check


def log(msg: str) -> None:
    base.log(f"mesh: {msg}")


def mesh_leaf(shape: tuple, ways: int, mesh: Mesh) -> tuple:
    """The host's part of one leaf: the one-chip shard with its cut
    dimension times the mesh's size, cut along it over both mesh axes."""
    part = base.shard_shape(shape, ways)
    (i,) = [k for k, (a, b) in enumerate(zip(shape, part)) if a != b]
    spec = P(*[AXES if k == i else None for k in range(len(shape))])
    return part[:i] + (part[i] * mesh.size,) + part[i + 1:], NamedSharding(mesh, spec)


@dataclasses.dataclass
class Sut(base.Sut):
    shardings: object = None  # the state's NamedShardings, a pytree like it


def setup(cell: harness.Cell, seed: int, seconds: float, rate: float = 0.0,
          control: bool = False) -> Sut:
    cfg = cell.config
    ways = int(cfg["fsdp_ways"])
    dtype = jnp.dtype(cfg["state_dtype"])
    rows, cols = cfg["mesh"]
    devices = jax.devices()[: rows * cols]
    mesh = Mesh(np.array(devices).reshape(rows, cols), AXES)
    full = base.leaf_shapes(cfg)
    shapes = jax.tree.map(lambda s: mesh_leaf(s, ways, mesh)[0], full, is_leaf=base.is_shape)
    sh = jax.tree.map(lambda s: mesh_leaf(s, ways, mesh)[1], full, is_leaf=base.is_shape)
    shardings = {"params": sh, "opt": {"m": sh, "v": sh, "step": NamedSharding(mesh, P())}}

    def make(key):
        leaves, treedef = jax.tree.flatten(shapes, is_leaf=base.is_shape)
        keys = jax.random.split(key, 3 * len(leaves))
        p = [0.02 * jax.random.normal(keys[i], s, dtype) for i, s in enumerate(leaves)]
        m = [1e-3 * jax.random.normal(keys[len(leaves) + i], s, dtype) for i, s in enumerate(leaves)]
        v = [1e-6 * jax.random.uniform(keys[2 * len(leaves) + i], s, dtype) for i, s in enumerate(leaves)]
        tree = lambda xs: jax.tree.unflatten(treedef, xs)  # noqa: E731
        return {"params": tree(p), "opt": {"m": tree(m), "v": tree(v),
                                           "step": jnp.zeros((), jnp.int32)}}

    def step(state, key):
        """``blob_ckpt_cycle``'s AdamW-shaped update: every element moves."""
        leaves, treedef = jax.tree.flatten(state["params"])
        keys = jax.random.split(key, len(leaves))
        g = [jax.random.normal(k, x.shape, x.dtype) for k, x in zip(keys, leaves)]
        m = [0.9 * a + 0.1 * b for a, b in zip(jax.tree.leaves(state["opt"]["m"]), g)]
        v = [0.999 * a + 0.001 * b * b for a, b in zip(jax.tree.leaves(state["opt"]["v"]), g)]
        p = [x - 1e-4 * a / (jnp.sqrt(b) + 1e-8) for x, a, b in zip(leaves, m, v)]
        tree = lambda xs: jax.tree.unflatten(treedef, xs)  # noqa: E731
        return {"params": tree(p), "opt": {"m": tree(m), "v": tree(v),
                                           "step": state["opt"]["step"] + 1}}

    # every program keeps the state laid out as the checkpointer's template
    make = jax.jit(make, out_shardings=shardings)
    step = jax.jit(step, donate_argnums=0, out_shardings=shardings)

    t0 = time.perf_counter()
    state = make(harness.device_key(seed))
    jax.block_until_ready(state)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
    per_chip = sum(s.data.nbytes for x in jax.tree.leaves(state) for s in x.addressable_shards
                   if s.device == devices[0])
    log(f"state of {n_bytes} bytes ({per_chip} on each of {len(devices)} chips, "
        f"{len(jax.tree.leaves(state))} leaves) made in {time.perf_counter() - t0:.2f} s")
    cluster = Cluster(**cfg["store"])
    ck = cfg["checkpointer"]
    ckpt = BlobCheckpointer(cluster.session(), state, page_size=int(ck["page_size"]),
                            keep_last=int(ck["keep_last"]))
    ref = harness.reference(cfg["reference"])
    sut = Sut(ckpt, state, step, jax.jit(ref.differing_elements), devices[0], n_bytes, ref,
              control, shardings=shardings)
    t0 = time.perf_counter()
    sut.state = sut.step(sut.state, jax.random.fold_in(harness.device_key(seed), 0))
    ckpt.save(0, sut.state)
    restored = ckpt.restore(shardings=shardings)
    jax.block_until_ready(restored)
    bad = int(sut.differing(restored, sut.state))
    if bad:
        raise RuntimeError(f"set-up restore differs from the saved state in {bad} elements")
    del restored
    if control:
        sut.lower = jax.jit(ref.lower_precision, out_shardings=shardings)
        jax.block_until_ready(sut.lower(sut.state))
    log(f"warm save and restore in {time.perf_counter() - t0:.2f} s")
    sut.key = harness.device_key(seed)
    return sut


def instrument(sut: Sut, spans: harness.Spans) -> List[str]:
    """``blob_ckpt_cycle``'s spans, on every writer's handle, and a span
    around each shard's part of a save and of a restore where the
    checkpointer saves shard by shard."""
    names = base.instrument(sut, spans)
    for handle in getattr(sut.ckpt, "shard_handles", {}).values():
        spans.wrap(handle, "write", "store_write")
        spans.wrap(handle, "read", "store_read")
    for attr, name in (("_save_shard", "shard_save"), ("_restore_shard", "shard_restore")):
        if hasattr(sut.ckpt, attr):
            spans.wrap(sut.ckpt, attr, name)
    return names + ["shard_save", "shard_restore"]


def drive(sut: Sut, seconds: float, spans: harness.Spans) -> dict:
    counts, records = [], []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        sut.step_no += 1
        sut.state = sut.step(sut.state, jax.random.fold_in(sut.key, sut.step_no))
        saved = sut.lower(sut.state) if sut.control else sut.state
        jax.block_until_ready(saved)
        with spans.span("ckpt_save"):
            records.append(sut.ckpt.save(sut.step_no, saved))
        del saved
        with spans.span("restore"):
            restored = sut.ckpt.restore(shardings=sut.shardings)
            jax.block_until_ready(restored)
        counts.append(sut.differing(restored, sut.state))
        del restored
    saves = [b - a for _, a, b, _ in spans.of("ckpt_save")]
    restores = [b - a for _, a, b, _ in spans.of("restore")]
    log(f"window: {len(saves)} saves and restores of {sut.n_bytes} bytes in "
        f"{time.perf_counter() - t0:.2f} s")
    return {"attempted": len(saves) + len(restores), "failed": 0,
            "save_s": saves, "restore_s": restores,
            "records": [dataclasses.asdict(r) for r in records],
            "differing": int(sum(int(c) for c in counts))}
