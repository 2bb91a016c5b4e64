"""Blob-backed KV serving: one ``ServingEngine`` whose KV pool is a blob on
one ``Cluster``, driven open-loop through ``submit``/``step``.

Set-up makes the weights on the device from the seed, builds the store and,
where the mix shares prefixes, has a peer engine prefill and publish every
shared document before it is freed. The serving engine then warms up every
prompt length the mix sends, the decode step, the fork of pages that two
live rows share, and the publish of a decode page that fills. Warm-up
prompts start with a document of their own, so the window's first use of
each shared document still reads its pages from the store.

The window submits each request when it falls due and steps the engine in
between; a token counts as delivered when the ``step`` that made it
returns. After the window every request is driven to its end, a minute at
most. Then:

* every page the engine fetched is compared byte for byte with what its
  publisher wrote at that version (limit: 0 pages differ);
* the engine is freed and the plain reference reruns a sample of finished
  requests, drawn from the seed and holding the longest, over prompt and
  served tokens: the widest gap by which a served token's logit lies below
  the reference's best must stay under the configuration's limit.

The control (``control=True``) puts the reference in float8 in the
program's place: at each served position of the same sample, the token the
float8 forward puts first is read in place of the served one, under the
same name and limit, so a sound control run comes out not correct.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import harness, load
from repro.core import Cluster
from repro.models.config import ModelConfig
from repro.models.lm import build_model
from repro.serving.blob_kv import BlobKVClient, BlobKVStore
from repro.serving.engine import Request, ServingEngine

DRAIN_S = 60.0  # a request still open at the close gets this long to finish
WARM_ID = 1 << 30  # warm-up request ids start here, clear of the window's
HOST_SPANS = ("admit", "kv_fetch", "kv_publish", "prefill", "decode", "sample")


def log(msg: str) -> None:
    print(f"[blob_kv_serve] {msg}", file=sys.stderr, flush=True)


class LoggedClient(BlobKVClient):
    """Keeps every published page (by slot and version) and every fetched
    page, so that fetches can be checked against what was published."""

    def __init__(self, store: BlobKVStore, published: Dict[tuple, np.ndarray]) -> None:
        super().__init__(store)
        self.published = published
        self.fetched: List[tuple] = []

    def publish_prompt(self, seq, payloads):
        versions = super().publish_prompt(seq, payloads)
        for i, buf in payloads.items():
            addr = seq.page_addr[i]
            self.published[(addr.page, addr.version)] = buf
        return versions

    def fetch_pages(self, addrs):
        bufs = super().fetch_pages(addrs)
        self.fetched.extend(zip(addrs, bufs))
        return bufs


def model_config(cfg: dict) -> ModelConfig:
    """The program's configuration for the sizes in the file."""
    if not cfg["tie_word_embeddings"]:
        raise ValueError("the serving program ties its head to the embedding table")
    sv = cfg["serving"]
    return ModelConfig(
        arch_id=cfg.get("name", "bench"), family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], rope_theta=cfg["rope_theta"],
        param_dtype=sv["param_dtype"], compute_dtype=sv["compute_dtype"],
        kv_cache_dtype=sv["kv_cache_dtype"], kv_page_tokens=sv["kv_page_tokens"],
        use_pallas=sv["use_pallas"],
    )


@dataclasses.dataclass
class Sut:
    cfg: dict
    mcfg: ModelConfig
    weights: object
    schedule: load.Schedule
    engine: Optional[ServingEngine]
    client: LoggedClient
    published: Dict[tuple, np.ndarray]
    ref: object
    seed: int
    control: bool
    trace_shapes: Optional[dict] = None


def setup(cell: harness.Cell, seed: int, seconds: float, rate: float = 0.0,
          control: bool = False) -> Sut:
    cfg, mix = cell.config, cell.traffic
    ref = harness.reference(cfg["reference"])
    mcfg = model_config(cfg)
    T, rows = mcfg.kv_page_tokens, int(cfg["serving"]["decode_rows"])

    t0 = time.perf_counter()
    weights = ref.make_weights(cfg, seed, jax.numpy.dtype(cfg["serving"]["param_dtype"]))
    jax.block_until_ready(weights)
    expect = jax.eval_shape(lambda k: build_model(mcfg).init(k)[0], jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), weights)
    if got != jax.tree.map(lambda x: (x.shape, x.dtype), expect):
        raise ValueError("reference weights are not laid out as the program takes them")
    log(f"weights made in {time.perf_counter() - t0:.2f} s")

    sched = load.schedule(mix, mcfg.vocab_size, seed, seconds, rate)
    if sched.max_prompt % T or any(t % T for t in sched.tails):
        raise ValueError("prompt lengths must be whole pages: the engine pads a "
                         "partial page and reads its first token after the padding")
    max_pages = -(-(sched.max_prompt + sched.max_new) // T) + 1
    cluster = Cluster(**cfg["store"])
    store = BlobKVStore.for_kv(
        cluster, n_pages=int(cfg["serving"]["pool_slots"]), page_tokens=T,
        n_layers=mcfg.n_layers, n_kv_heads=mcfg.n_kv_heads, head_dim=mcfg.head_dim,
        dtype=np.uint16,  # bf16 pages travel as 2-byte payloads
    )
    rng = np.random.default_rng([seed, 7])
    warm_doc = rng.integers(0, mcfg.vocab_size, len(sched.prefixes[0])).tolist() \
        if sched.prefixes else []
    published: Dict[tuple, np.ndarray] = {}
    kw = dict(max_slots=rows, max_pages_per_seq=max_pages)

    if sched.prefixes:
        t0 = time.perf_counter()
        peer = ServingEngine(mcfg, weights, kv_client=LoggedClient(store, published), **kw)
        for i, doc in enumerate(sched.prefixes + [warm_doc]):
            peer.submit(Request(WARM_ID + i, doc, max_new_tokens=1))
        peer.run_until_drained()
        del peer
        gc.collect()
        log(f"peer published {len(published)} pages in {time.perf_counter() - t0:.2f} s")

    t0 = time.perf_counter()
    client = LoggedClient(store, published)
    engine = ServingEngine(mcfg, weights, kv_client=client, **kw)
    warm = [warm_doc + rng.integers(0, mcfg.vocab_size, t).tolist() for t in sched.tails]
    warm.append(warm_doc + rng.integers(0, mcfg.vocab_size, sched.tails[0]).tolist())
    for i, prompt in enumerate(warm):
        # one request crosses a page boundary while decoding, which publishes a page
        new = T + 2 if i == 0 else 2
        engine.submit(Request(WARM_ID + 100 + i, prompt, max_new_tokens=new))
    engine.run_until_drained()
    client.fetched.clear()
    log(f"engine warmed {len(warm)} prompts in {time.perf_counter() - t0:.2f} s; "
        f"pool {store.n_pages} slots, {store.used_slots} in use")
    return Sut(cfg, mcfg, weights, sched, engine, client, published, ref, seed, control)


def instrument(sut: Sut, spans: harness.Spans) -> List[str]:
    """Host spans around the calls into each layer (traced runs only)."""
    eng, client = sut.engine, sut.client
    decode_rows: List[List[int]] = []
    prefill_lens: List[int] = []

    def prefill_done(out, args, attrs):
        jax.block_until_ready(out)
        prefill_lens.append(int(args[1].shape[1]))

    def decode_done(out, args, attrs):
        jax.block_until_ready(out[0])
        held = np.asarray(args[5])  # tokens cached per row, before this step
        decode_rows.append([int(n) + 1 for n in held if n > 0])

    spans.wrap(eng, "_admit", "admit")
    spans.wrap(client, "admit", "kv_admit")
    spans.wrap(client, "fetch_pages", "kv_fetch")
    spans.wrap(client, "publish_prompt", "kv_publish")
    spans.wrap(eng, "_jit_prefill_tokens", "prefill", prefill_done)
    spans.wrap(eng, "_jit_decode", "decode", decode_done)
    spans.wrap(eng, "_sample", "sample")
    sut.trace_shapes = {"prefill_lens": prefill_lens, "decode_rows": decode_rows}
    return list(HOST_SPANS)


def drive(sut: Sut, seconds: float, spans: harness.Spans) -> dict:
    """The open-loop window. Returns what the end-to-end metrics read."""
    eng, reqs = sut.engine, sut.schedule.requests
    n = len(reqs)
    first: List[Optional[float]] = [None] * n
    last: List[Optional[float]] = [None] * n
    late: List[float] = []
    served, seen = 0, len(eng._done)
    backlog = None
    t0 = time.perf_counter()
    deadline = t0 + max(seconds, reqs[-1].due_s) + DRAIN_S
    i = 0
    while True:
        now = time.perf_counter()
        while i < n and t0 + reqs[i].due_s <= now:
            r = reqs[i]
            eng.submit(Request(i, r.prompt, max_new_tokens=r.new_tokens))
            late.append(now - (t0 + r.due_s))
            i += 1
        active = eng.step()
        now = time.perf_counter()
        if backlog is None and now >= t0 + seconds:
            backlog = eng._queue.qsize() + (n - i)  # due or soon due, not yet admitted
        for slot in eng._slots:
            if slot is not None and first[slot["req"].request_id] is None:
                first[slot["req"].request_id] = now
        if len(eng._done) != seen:
            seen = len(eng._done)
            for rid in eng._done:
                if rid < n and last[rid] is None:
                    last[rid] = now
                    first[rid] = first[rid] or now
                    served += 1
        if served == n or now > deadline:
            break
        if not active and eng._queue.empty() and i < n:
            time.sleep(max(0.0, t0 + reqs[i].due_s - time.perf_counter()))
    end = time.perf_counter()
    tokens = {rid: list(eng._done[rid].tokens) for rid in range(n) if last[rid] is not None}
    ttft = [(first[k] - (t0 + reqs[k].due_s)) if first[k] is not None and k in tokens
            else math.inf for k in range(n)]
    span = sum(last[k] - first[k] for k in tokens)
    gaps = sum(len(tokens[k]) - 1 for k in tokens)
    stats = sut.client.store.stats
    half = sorted(ttft[: n // 2]), sorted(ttft[n // 2:])
    log(f"window: {n} requests, {len(tokens)} finished in {end - t0:.2f} s; backlog at "
        f"the close {backlog}; median ttft s, first half {half[0][len(half[0]) // 2]:.3f}, "
        f"second half {half[1][len(half[1]) // 2]:.3f}; {len(sut.client.fetched)} pages "
        f"fetched; store {stats}")
    return {
        "attempted": n, "failed": n - len(tokens), "ttft_s": ttft, "late_s": late,
        "tpot_s": span / gaps if gaps else math.inf, "tokens": tokens,
        "elapsed_s": end - t0, "backlog": backlog,
    }


def check(sut: Sut, window: dict) -> List[dict]:
    """Compare what the window produced with what it must be. Frees the
    engine first; the reference runs afterwards."""
    bad = 0
    for addr, buf in sut.client.fetched:
        want = sut.published.get((addr.page, addr.version))
        if want is None or not np.array_equal(np.asarray(buf), want):
            bad += 1
    checks = [{"name": "fetched_pages_differing", "value": bad, "limit": 0}]
    n_fetched = len(sut.client.fetched)
    sut.engine = None
    sut.client.fetched.clear()
    sut.published.clear()
    gc.collect()

    limit = sut.cfg["check"]["served_logit_gap"]
    tokens: Dict[int, List[int]] = window["tokens"]
    if not tokens:
        checks.append({"name": "served_logit_gap", "value": math.inf, "limit": limit})
        return checks
    reqs = sut.schedule.requests
    k = int(sut.cfg["check"]["sample_requests"])
    longest = max(tokens, key=lambda r: len(reqs[r].prompt) + len(tokens[r]))
    rest = sorted(r for r in tokens if r != longest)
    rng = np.random.default_rng([sut.seed, 11])
    sample = [longest] + sorted(rng.choice(rest, min(k - 1, len(rest)), replace=False).tolist())
    pad_to = -(-(sut.schedule.max_prompt + sut.schedule.max_new) // 128) * 128

    def widest(quant=None) -> float:
        fn = sut.ref.gap_fn(sut.cfg, pad_to, quant=quant)
        return max(float(sut.ref.served_gaps(fn, sut.weights, reqs[r].prompt, tokens[r],
                                             pad_to).max()) for r in sample)

    t0 = time.perf_counter()
    gap = widest()
    n_tok = sum(len(tokens[r]) for r in sample)
    log(f"reference over {len(sample)} requests ({n_tok} served tokens) in "
        f"{time.perf_counter() - t0:.2f} s; {n_fetched} fetched pages compared; "
        f"served_logit_gap {gap!r}")
    if sut.control:
        gap = widest("fp8")
        log(f"control: float8 reference in the program's place reads {gap!r}")
    checks.append({"name": "served_logit_gap", "value": gap, "limit": limit})
    return checks
