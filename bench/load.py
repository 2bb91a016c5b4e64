"""The one generator of prompt traffic: a mix file of parameters in, an
open-loop schedule of requests out.

Every seed gets the same work in the same order: the arrival times, the
prompt and output lengths and which shared document each request starts
with come from one fixed stream, independent of the seed. The seed draws
the token ids: the documents, each request's own tokens and so everything
the model serves. At four fifths of the knee, the order of a burst moves
the tail of time to first token by a third or more from one ordering to
the next, so a seed that reordered the work would measure its order, not
the system.

A mix file (``bench/traffic/<mix>.json``) gives:

``rate_per_s``
    the offered load; a run of ``S`` seconds holds ``round(rate * S)``
    requests, due in ``[0, S)``, with Poisson arrivals (the gaps are the
    exponential's quantiles, in the fixed stream's order).
``shared_prefixes``
    ``{"count": n, "tokens": t}``: ``n`` documents of ``t`` tokens, each the
    start of as many requests as the others. ``count`` 0 shares nothing.
``tail_tokens``, ``new_tokens``
    the length of each request's own part of the prompt, and of its
    output: ``{"median": m, "sigma": s, "min": a, "max": b, "step": k}``, a
    lognormal taken at its quantiles, one a request, rounded to a multiple
    of ``k`` and held to ``[a, b]``.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import List

import numpy as np


@dataclasses.dataclass
class PromptRequest:
    index: int
    due_s: float  # offset from the start of the window
    prefix: int  # shared document, or -1
    prompt: List[int]
    new_tokens: int


@dataclasses.dataclass
class Schedule:
    requests: List[PromptRequest]
    prefixes: List[List[int]]  # the shared documents
    tails: List[int]  # every tail length sent, ascending
    max_prompt: int
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths of a lognormal, one at each of ``n`` even quantiles."""
    z = np.array([statistics.NormalDist().inv_cdf(q) for q in _quantiles(n)])
    raw = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    k = int(dist["step"])
    return np.clip(np.rint(raw / k).astype(int) * k, int(dist["min"]), int(dist["max"]))


def schedule(mix: dict, vocab: int, seed: int, seconds: float, rate: float = 0.0) -> Schedule:
    """Requests due in a window of ``seconds``; ``rate`` overrides the
    mix's own (for a sweep)."""
    rate = rate or float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(seed)
    pre = mix["shared_prefixes"]
    n_pre, pre_len = int(pre["count"]), int(pre["tokens"])

    fixed = np.random.default_rng(0)  # the work: the same for every seed
    tail_len = fixed.permutation(lengths(mix["tail_tokens"], n))
    new = fixed.permutation(lengths(mix["new_tokens"], n))
    which = fixed.permutation([i % n_pre for i in range(n)]) if n_pre else np.full(n, -1)
    gaps = -np.log1p(-_quantiles(n))
    due = np.cumsum(fixed.permutation(gaps / gaps.mean() / rate))
    due = due - due[0]  # the first request is due as the window opens
    prefixes = [rng.integers(0, vocab, pre_len).tolist() for _ in range(n_pre)]

    requests = []
    for i in range(n):
        p = int(which[i])
        head = prefixes[p] if p >= 0 else []
        prompt = head + rng.integers(0, vocab, int(tail_len[i])).tolist()
        requests.append(PromptRequest(i, float(due[i]), p, prompt, int(new[i])))
    tails = sorted({int(t) for t in tail_len})
    return Schedule(requests, prefixes, tails, pre_len + tails[-1], int(new.max()))
