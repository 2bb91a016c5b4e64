"""The trace reduction, on a trace recorded on a TPU v5e: two Yi-9B-wide
layers serving a few requests through the blob-backed engine."""

import gzip
import json
from pathlib import Path

import pytest

from bench import trace as btrace

DATA = Path(__file__).parent / "data" / "probe_trace.json.gz"


@pytest.fixture(scope="module")
def events():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


def _inside(events, kind):
    (w,) = [e for e in events if e["kind"] == "host" and e["name"] == btrace.WINDOW]
    return [e for e in events if e["kind"] == kind and w["t0"] <= e["t0"] < w["t0"] + w["dur"]]


def test_program_times_are_the_module_events(events):
    r = btrace.reduce(events)
    mods = _inside(events, "module")
    want = sum(e["dur"] for e in mods if e["name"] == "jit__prefill_tokens_impl") * 1e-9
    assert r.program_s("_prefill_tokens_impl") == pytest.approx(want)
    assert r.module_runs["jit__prefill_tokens_impl"] == 3
    assert r.module_runs["jit__decode_impl"] == 7
    assert r.program_s("no_such_program") is None


def test_pallas_time_is_attributed_to_its_program(events):
    r = btrace.reduce(events)
    pallas = [e for e in _inside(events, "op") if e["pallas"]]
    assert sum(r.pallas_s.values()) == pytest.approx(sum(e["dur"] for e in pallas) * 1e-9)
    assert set(r.pallas_s) == {"jit__prefill_tokens_impl", "jit__decode_impl"}
    for prog in r.pallas_s:
        assert 0 < r.kernel_s(prog) < r.program_s(prog)


def test_busy_and_idle_cover_the_window(events):
    r = btrace.reduce(events)
    assert 0 < r.busy_s < r.window_s
    idle = sum(s for _, s in r.idle_gaps)
    assert r.busy_s + idle == pytest.approx(r.window_s, rel=1e-9)
    assert r.busy_s >= max(r.module_s.values())


def test_idle_gaps_are_labelled_by_host_spans(events):
    (w,) = [e for e in events if e["kind"] == "host" and e["name"] == btrace.WINDOW]
    first_op = min(e["t0"] for e in _inside(events, "op"))
    half = (first_op - w["t0"]) / 2
    spans = [
        {"kind": "host", "name": "admit", "t0": w["t0"], "dur": half},
        {"kind": "host", "name": "kv_fetch", "t0": w["t0"] + half, "dur": half},
        # an outer span: the inner one names the time it covers
        {"kind": "host", "name": "step", "t0": w["t0"], "dur": 2 * half},
    ]
    labels = dict(btrace.reduce(events + spans).idle_gaps)
    assert labels["admit"] == pytest.approx(half * 1e-9)
    assert labels["kv_fetch"] == pytest.approx(half * 1e-9)
    assert "step" not in labels


def test_breakdown_is_short_and_loops_are_not_double_counted(events):
    r = btrace.reduce(events)
    out = r.breakdown()
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert not any(name.split("/")[-1].startswith("while") for name, _ in out["device_ops"])
    assert sum(s for _, s in out["device_ops"]) <= r.busy_s


def test_a_trace_without_its_window_is_refused(events):
    with pytest.raises(ValueError):
        btrace.reduce([e for e in events if e["kind"] != "host"])
