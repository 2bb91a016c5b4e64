"""Bring-up check on a TPU: the repository's main path, run once at the full
width of llama3.2-1b with random weights made from ``--seed``.

    python chip_smoke.py                # one chip: serving + checkpoint restore
    python chip_smoke.py --four-chips   # four chips: sharded training only

One chip:

* **serving** — two ``ServingEngine``s (A, then B) share one blob-backed KV
  pool (``BlobKVStore``, 512 slots of 2 MiB) on one ``Cluster``, with the
  Pallas kernels on (``use_pallas=True``). Eight requests share a
  1024-token system prompt; A publishes its prefix pages and B reads them
  back from the blob store. Checks: (a) every page B fetched is byte for
  byte what A's device pool held in that slot at publish; (b) the paged and
  flash Pallas kernels match ``ref`` on B's live pool and tables and on a
  prefill-sized input; (c) every request's first-token logits match a
  host-allocator engine on the XLA attention path; (d) the compiled decode
  step holds a Pallas kernel (``tpu_custom_call``).
* **checkpoint** — the served params are saved through ``BlobCheckpointer``
  and restored onto the chip, byte-exact.

Four chips: ``launch.train.train`` at full size on a 2x2 mesh with one
checkpoint in the blob store, restored onto the same mesh byte-exact; then a
2-layer variant at full width on one chip and on the mesh, whose losses must
agree.

Everything runs in this one process, which holds the chip. Any failed check
raises, so the exit code is non-zero; a passing run's last line is one JSON
object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import Cluster  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.models.lm import build_model  # noqa: E402
from repro.serving.blob_kv import BlobKVClient, BlobKVStore, pack_kv_page  # noqa: E402
from repro.serving.engine import Request, ServingEngine  # noqa: E402
from repro.storage.checkpoint import BlobCheckpointer  # noqa: E402

ARCH = "llama3_2-1b"
POOL_SLOTS = 512  # x 2 MiB a slot: a 1 GiB pool blob, mirrored in each engine's HBM
SYSTEM_TOKENS = 1024
TAILS = (64, 128, 320, 448)  # per engine: padded 1088, 1152, 1344, 1472 tokens
NEW_TOKENS = 32
FLASH_SEQ = 1216  # a page-padded prompt length that 128 does not divide
# bf16 tolerances of tests/test_kernels.py
FLASH_TOL, PAGED_TOL = 2e-2, 3e-2
LOGITS_TOL = 5e-2  # of the largest reference logit; 1.1e-2 to 1.9e-2 at cut sizes on CPU
TRAIN = dict(steps=3, batch=4, seq=128, checkpoint_every=3)
MODEL_PARALLEL = 2  # the 2x2 mesh: 2-way data x 2-way model
LOSS_RTOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found {dev.platform}")
    log(f"device: {dev.device_kind}, count {len(jax.devices())}")
    return dev


def peak_bytes(dev: jax.Device) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def bits_equal(a, b) -> bool:
    """Every leaf of two pytrees holds the same bytes (compared on device)."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if jax.tree.structure(a) != jax.tree.structure(b):
        return False
    if any(x.shape != y.shape or x.dtype != y.dtype for x, y in zip(la, lb)):
        return False
    uint = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}

    def same(xs, ys):
        return jnp.stack([
            jnp.all(jax.lax.bitcast_convert_type(x, uint[x.dtype.itemsize])
                    == jax.lax.bitcast_convert_type(y, uint[y.dtype.itemsize]))
            for x, y in zip(xs, ys)
        ]).all()

    return bool(jax.jit(same)(la, lb))


# --------------------------------- serving ---------------------------------
class PublishLog(BlobKVClient):
    """Engine A's client: keeps, per published ``(slot, version)``, the bytes
    A's device pool held in that slot when the page was published."""

    def __init__(self, store: BlobKVStore) -> None:
        super().__init__(store)
        self.engine: ServingEngine = None  # set once the engine exists
        self.published = {}

    def publish_prompt(self, seq, payloads):
        pool_k, pool_v = self.engine.pool_k, self.engine.pool_v
        held = {
            i: pack_kv_page(pool_k[:, seq.slots[i]], pool_v[:, seq.slots[i]],
                            self.store.page_size)
            for i in payloads
        }
        versions = super().publish_prompt(seq, payloads)
        for i, buf in held.items():
            addr = seq.page_addr[i]
            self.published[(addr.page, addr.version)] = buf
        return versions


class FetchLog(BlobKVClient):
    """Engine B's client: keeps every page it fetched from the blob store."""

    def __init__(self, store: BlobKVStore) -> None:
        super().__init__(store)
        self.fetched = []

    def fetch_pages(self, addrs):
        bufs = super().fetch_pages(addrs)
        self.fetched.extend((a, np.array(b, copy=True)) for a, b in zip(addrs, bufs))
        return bufs


class StepLog:
    """Wraps an engine's jitted prefill and decode: keeps each prompt's
    first-token logits, and the inputs of its first decode step."""

    def __init__(self, engine: ServingEngine) -> None:
        self.prefill, self.decode = engine._jit_prefill_tokens, engine._jit_decode
        engine._jit_prefill_tokens, engine._jit_decode = self._prefill, self._decode
        self.first_logits = {}
        self.decode_args = None

    def _prefill(self, params, tokens):
        out = self.prefill(params, tokens)
        self.first_logits[np.asarray(tokens).tobytes()] = np.asarray(out[0][0], np.float32)
        return out

    def _decode(self, *args):
        if self.decode_args is None:
            self.decode_args = args
        return self.decode(*args)


def serve(engine: ServingEngine, prompts, first_id: int):
    for i, prompt in enumerate(prompts):
        engine.submit(Request(first_id + i, prompt, max_new_tokens=NEW_TOKENS))
    done = engine.run_until_drained()
    if len(done) != len(prompts) or any(len(c.tokens) != NEW_TOKENS for c in done.values()):
        raise AssertionError(f"engine served {len(done)} of {len(prompts)} requests")
    return done


def check_kernels(cfg, step_log: StepLog, seed: int) -> None:
    """(b): Pallas against ref on B's live pool and tables, and on a
    prefill-sized flash input."""
    _, pool_k, pool_v, tables, page_pos, lengths, _ = step_log.decode_args
    live = np.asarray(lengths) > 0
    kq, kk, kv, kf = jax.random.split(jax.random.PRNGKey(seed), 4)
    H, K, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jax.random.normal(kq, (tables.shape[0], H, D), jnp.float32).astype(jnp.bfloat16)
    args = (q, pool_k[0], pool_v[0], tables, page_pos, lengths)
    got = np.asarray(ops.paged_attention(*args, impl="pallas"), np.float32)[live]
    want = np.asarray(ops.paged_attention(*args, impl="ref"), np.float32)[live]
    np.testing.assert_allclose(got, want, rtol=PAGED_TOL, atol=PAGED_TOL)
    log(f"  (b) paged pallas == ref on B's pool: {int(live.sum())} rows, "
        f"max |diff| {np.abs(got - want).max():.3g}")

    q = jax.random.normal(kk, (1, FLASH_SEQ, H, D), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(kv, (1, FLASH_SEQ, K, D), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(kf, (1, FLASH_SEQ, K, D), jnp.float32).astype(jnp.bfloat16)
    got = np.asarray(ops.flash_attention(q, k, v, causal=True, impl="pallas"), np.float32)
    want = np.asarray(ops.flash_attention(q, k, v, causal=True, impl="ref"), np.float32)
    np.testing.assert_allclose(got, want, rtol=FLASH_TOL, atol=FLASH_TOL)
    log(f"  (b) flash pallas == ref at {FLASH_SEQ} tokens: max |diff| "
        f"{np.abs(got - want).max():.3g}")


def check_decode_kernel(step_log: StepLog) -> None:
    """(d): the decode step the engine compiled runs a Pallas kernel."""
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        step_log.decode_args,
    )
    if "tpu_custom_call" not in step_log.decode.lower(*shapes).compile().as_text():
        raise AssertionError("compiled decode step holds no tpu_custom_call")
    log("  (d) compiled decode step holds tpu_custom_call")


def serving_phase(dev: jax.Device, seed: int):
    """Returns the cluster and the served params for the checkpoint phase."""
    cfg = dataclasses.replace(get_config(ARCH), use_pallas=True)
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.jit(lambda k: model.init(k)[0])(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    log(f"serving: {ARCH} params made in {time.perf_counter() - t0:.1f} s")

    cluster = Cluster(n_data_providers=4, n_metadata_providers=4)
    store = BlobKVStore.for_kv(
        cluster, n_pages=POOL_SLOTS, page_tokens=cfg.kv_page_tokens,
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        dtype=np.uint16,  # bf16 pages travel as 2-byte payloads
    )
    log(f"  pool blob: {POOL_SLOTS} slots x {store.page_size >> 20} MiB")

    rng = np.random.default_rng(seed)
    system = rng.integers(0, cfg.vocab_size, SYSTEM_TOKENS).tolist()
    prompts = [system + rng.integers(0, cfg.vocab_size, n).tolist() for n in TAILS * 2]
    max_pages = -(-(SYSTEM_TOKENS + max(TAILS) + NEW_TOKENS) // cfg.kv_page_tokens) + 1
    engine_kw = dict(max_slots=4, max_pages_per_seq=max_pages)

    t0 = time.perf_counter()
    client_a = PublishLog(store)
    engine_a = ServingEngine(cfg, params, kv_client=client_a, **engine_kw)
    client_a.engine = engine_a
    log_a = StepLog(engine_a)
    done_a = serve(engine_a, prompts[: len(TAILS)], 0)
    first_a = log_a.first_logits
    # frees A's device pool; its published pages stay in the blob
    client_a.engine = None
    del engine_a, log_a

    client_b = FetchLog(store)
    engine_b = ServingEngine(cfg, params, kv_client=client_b, **engine_kw)
    log_b = StepLog(engine_b)
    done_b = serve(engine_b, prompts[len(TAILS):], len(TAILS))
    seconds = time.perf_counter() - t0
    done = {**done_a, **done_b}
    tokens = sum(len(c.tokens) for c in done.values())
    shared = sum(c.prefill_skipped_tokens for c in done.values())
    log(f"  served {len(done)} requests, {tokens} tokens, {shared} prefix tokens "
        f"shared ({sum(c.prefill_skipped_tokens for c in done_b.values())} by B "
        f"from A's pages) in {seconds:.1f} s incl. compiles; peak device bytes "
        f"{peak_bytes(dev)}")

    # (a) B's fetches are A's published device bytes
    system_pages = SYSTEM_TOKENS // cfg.kv_page_tokens
    if len(client_b.fetched) < system_pages:
        raise AssertionError(f"B fetched {len(client_b.fetched)} pages, "
                             f"expected at least the {system_pages} system-prompt pages")
    for addr, buf in client_b.fetched:
        held = client_a.published.get((addr.page, addr.version))
        if held is None or not np.array_equal(buf, held):
            raise AssertionError(f"page {addr} fetched by B is not what A published")
    log(f"  (a) {len(client_b.fetched)} pages B fetched == A's device pool at publish")

    check_kernels(cfg, log_b, seed)  # (b)
    check_decode_kernel(log_b)  # (d)
    first_b = log_b.first_logits
    del engine_b, log_b

    # (c) first-token logits against the host-allocator engine on XLA attention
    ref_engine = ServingEngine(dataclasses.replace(cfg, use_pallas=False), params,
                               **engine_kw)
    log_ref = StepLog(ref_engine)
    serve(ref_engine, prompts, 0)
    worst = 0.0
    served = {**first_a, **first_b}
    for key, want in log_ref.first_logits.items():
        got = served.get(key)
        if got is None:
            raise AssertionError("a reference prompt was never prefilled by A or B")
        err = float(np.abs(got - want).max() / np.abs(want).max())
        worst = max(worst, err)
        if err > LOGITS_TOL:
            raise AssertionError(f"first-token logits differ by {err:.3g} of scale")
    log(f"  (c) first-token logits of {len(log_ref.first_logits)} requests == XLA "
        f"engine: worst max|diff|/max|ref| {worst:.3g}")
    return cluster, params


# -------------------------------- checkpoint --------------------------------
def checkpoint_phase(dev: jax.Device, cluster: Cluster, params) -> None:
    ckpt = BlobCheckpointer(cluster.session(), params)
    t0 = time.perf_counter()
    ckpt.save(0, params)
    t1 = time.perf_counter()
    restored = ckpt.restore(shardings=SingleDeviceSharding(dev))
    jax.block_until_ready(restored)
    t2 = time.perf_counter()
    if any(leaf.devices() != {dev} for leaf in jax.tree.leaves(restored)):
        raise AssertionError("restored params are not on the chip")
    if not bits_equal(restored, params):
        raise AssertionError("restored params differ from the served params")
    n_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    log(f"checkpoint: {n_bytes} bytes saved in {t1 - t0:.1f} s, restored onto the "
        f"chip in {t2 - t1:.1f} s, byte-exact; peak device bytes {peak_bytes(dev)}")


# -------------------------------- four chips --------------------------------
def four_chip_phase(seed: int) -> None:
    if len(jax.devices()) != 4:
        raise AssertionError(f"--four-chips needs 4 devices, found {len(jax.devices())}")
    t0 = time.perf_counter()
    out = train(ARCH, seed=seed, model_parallel=MODEL_PARALLEL, **TRAIN)
    state = {"params": out["params"], "opt": out["opt_state"]}
    shardings = jax.tree.map(lambda x: x.sharding, state)
    restored = out["checkpointer"].restore(shardings=shardings)
    if jax.tree.map(lambda x: x.sharding, restored) != shardings:
        raise AssertionError("restored state is not laid out like the trained state")
    if not bits_equal(restored, state):
        raise AssertionError("restored training state differs from the trained state")
    n_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
    per_chip = max(peak_bytes(d) for d in jax.devices())
    log(f"train: {ARCH} on a 2x2 mesh, losses {out['losses']}, {n_bytes} state "
        f"bytes checkpointed and restored onto the mesh byte-exact in "
        f"{time.perf_counter() - t0:.1f} s; peak device bytes {per_chip}")
    del out, state, restored

    one = train(ARCH, seed=seed, n_layers=2, n_devices=1, **TRAIN)["losses"]
    mesh = train(ARCH, seed=seed, n_layers=2, model_parallel=MODEL_PARALLEL,
                 **TRAIN)["losses"]
    np.testing.assert_allclose(mesh, one, rtol=LOSS_RTOL)
    log(f"train: 2-layer losses on one chip {one} == on the mesh {mesh}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training path on a 2x2 mesh")
    args = ap.parse_args()

    dev = require_tpu()
    use_compile_cache()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        cluster, params = serving_phase(dev, args.seed)
        checkpoint_phase(dev, cluster, params)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
