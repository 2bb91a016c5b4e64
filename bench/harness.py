"""What every cell shares: finding the pieces named in ``BENCHMARK.json``,
host spans, the chip check, and the result line.

A cell names a configuration (``bench/configs/<config>.json``), a traffic
mix (``bench/traffic/<traffic>.json``) and its metrics (``bench/metrics/
<metric>.py``). The configuration names the system that drives it
(``bench/systems/<system>.py``) and its plain reference
(``bench/refs/<reference>.py``). Nothing here knows a cell by name, so a
later cell, mix or metric is added by adding files and entries.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional, Sequence

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import one file by path; metric names hold dots, so they are not
    importable as package members."""
    name = "bench._" + str(path.relative_to(BENCH_DIR).with_suffix("")).replace("/", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up while it loads
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, benchmark: dict, name: str) -> None:
        by_name = {w["name"]: w for w in benchmark["workloads"]}
        if name not in by_name:
            raise SystemExit(f"unknown workload {name!r}; known: {sorted(by_name)}")
        self.workload = by_name[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        entry = {c["name"]: c for c in benchmark["configs"]}[self.workload["config"]]
        self.config = load_json(ROOT / entry["file"])
        self.traffic = load_json(BENCH_DIR / "traffic" / f"{self.workload['traffic']}.json")
        self.end_to_end = [m for m in benchmark["end_to_end"] if self._reports(m)]
        self.per_layer = [m for m in benchmark["per_layer"] if self._reports(m)]

    def _reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def system(self) -> ModuleType:
        return load_module(BENCH_DIR / "systems" / f"{self.config['system']}.py")


def reference(name: str) -> ModuleType:
    return load_module(BENCH_DIR / "refs" / f"{name}.py")


def metric_reader(name: str) -> Callable[["Run"], Optional[float]]:
    return load_module(BENCH_DIR / "metrics" / f"{name}.py").read


# ---------------------------------- spans ----------------------------------
class Spans:
    """Host spans kept in memory: ``(name, start, end, attrs)`` on the
    ``time.perf_counter`` clock. When the profiler runs, each span is also a
    ``TraceAnnotation``, so the trace can say what the host did in a gap."""

    def __init__(self, annotate: bool = False) -> None:
        self.annotate = annotate
        self.records: List[tuple] = []

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        try:
            with ann:
                yield attrs
        finally:
            self.records.append((name, t0, time.perf_counter(), attrs))

    def wrap(self, obj: Any, attr: str, name: str,
             after: Optional[Callable[[Any, tuple, dict], None]] = None) -> None:
        """Replace ``obj.attr`` by a call that records a span around it.
        ``after(result, args, attrs)`` runs inside the span (to wait for a
        device result, or to note what the call was given)."""
        inner = getattr(obj, attr)

        def wrapped(*args, **kwargs):
            with self.span(name) as attrs:
                out = inner(*args, **kwargs)
                if after is not None:
                    after(out, args, attrs)
            return out

        setattr(obj, attr, wrapped)

    def of(self, name: str) -> List[tuple]:
        return [r for r in self.records if r[0] == name]

    def total(self, name: str) -> float:
        return sum(r[2] - r[1] for r in self.of(name))

    def self_time(self, parent: str, children: Sequence[str]) -> float:
        """Summed duration of ``parent`` spans minus the part of each that
        its ``children`` spans cover."""
        kids = sorted((r[1], r[2]) for r in self.records if r[0] in children)
        out = 0.0
        for _, t0, t1, _ in self.of(parent):
            covered, cursor = 0.0, t0
            for a, b in kids:
                a, b = max(a, cursor), min(b, t1)
                if b > a:
                    covered += b - a
                    cursor = b
            out += (t1 - t0) - covered
        return out


def device_key(seed: int):
    """A JAX key from a seed of any size: ``jax.random.key`` keeps only the
    low 32 bits of a larger one, so the high bits are folded in."""
    import jax

    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


# --------------------------------- numbers ---------------------------------
def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``% of
    the values at or below it. Infinite values (misses) sort last."""
    if not values:
        raise ValueError("percentile of no values")
    ranked = sorted(values)
    return ranked[max(0, math.ceil(q / 100.0 * len(ranked)) - 1)]


class Run:
    """What one run gives the metric readers."""

    def __init__(self, cell: Cell, peaks: dict) -> None:
        self.cell = cell
        self.peaks = peaks
        self.setup_s: float = 0.0
        self.window: Dict[str, Any] = {}
        self.spans = Spans()
        self.sut = None  # what the system's setup returned
        self.trace = None  # bench.trace.Reduction in a traced run
