"""From a profiler trace to the numbers the per-layer metrics read.

:func:`capture` runs the profiler around a block with the Python tracer off
and reads the trace back as plain events (:func:`events_from_xspace`).
:func:`reduce` turns those events into a :class:`Reduction`: device busy
time, device time of each jitted program, time of the Pallas kernels inside
each program, and the ``breakdown`` the result line carries. The events are
plain dicts, so a recorded trace checks the reduction without a chip
(``bench/tests/test_trace.py``).

Device events come from the ``XLA Modules`` and ``XLA Ops`` lines of each
``/device:TPU:<n>`` plane. Host spans are the benchmark's
``TraceAnnotation``s; the profiler puts host and device events on one clock.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Sequence

WINDOW = "bench_window"  # the host span around the traced window
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
_MODULE_HASH = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """``jit__decode_impl(1451...)`` -> ``jit__decode_impl``."""
    return _MODULE_HASH.sub("", name)


def op_name(hlo_text: str) -> str:
    """``%fusion.73 = bf16[...] fusion(...)`` -> ``fusion.73``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # union of op intervals in the window, mean over chips
    module_s: Dict[str, float]  # device time of each program, by name
    module_runs: Dict[str, int]
    pallas_s: Dict[str, float]  # Pallas kernel time inside each program
    device_ops: List[list]
    idle_gaps: List[list]

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops, "idle_gaps": self.idle_gaps}

    def program_s(self, fragment: str) -> Optional[float]:
        """Device time of the programs whose name holds ``fragment``, or
        ``None`` where none ran in the window."""
        hits = [s for m, s in self.module_s.items() if fragment in m]
        return sum(hits) if hits else None

    def kernel_s(self, fragment: str) -> Optional[float]:
        hits = [s for m, s in self.pallas_s.items() if fragment in m]
        return sum(hits) if hits else None


def events_from_xspace(path: str, host_names: Iterable[str]) -> List[dict]:
    """Read an ``.xplane.pb`` into plain events: device modules and ops, and
    host events whose name is one of ``host_names``."""
    from jax.profiler import ProfileData

    host_names = set(host_names) | {WINDOW}
    out: List[dict] = []
    data = ProfileData.from_file(path)
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = plane.name
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        out.append({"kind": "module", "chip": chip, "name": module_name(e.name),
                                    "t0": e.start_ns, "dur": e.duration_ns})
                elif line.name == "XLA Ops":
                    for e in line.events:
                        out.append({"kind": "op", "chip": chip, "name": op_name(e.name),
                                    "pallas": PALLAS_TARGET in e.name,
                                    "t0": e.start_ns, "dur": e.duration_ns})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host_names:
                        out.append({"kind": "host", "name": e.name,
                                    "t0": e.start_ns, "dur": e.duration_ns})
    return out


def _union(intervals: List[tuple]) -> List[tuple]:
    merged: List[list] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [tuple(m) for m in merged]


def _top(totals: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def reduce(events: Sequence[dict]) -> Reduction:
    """Reduce plain events (see :func:`events_from_xspace`) to a
    :class:`Reduction` over the ``bench_window`` host span."""
    windows = [e for e in events if e["kind"] == "host" and e["name"] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} {WINDOW!r} spans, expected 1")
    w0, w1 = windows[0]["t0"], windows[0]["t0"] + windows[0]["dur"]
    chips = sorted({e["chip"] for e in events if e["kind"] in ("module", "op")})
    if not chips:
        raise ValueError("trace holds no device events")
    host = [e for e in events if e["kind"] == "host" and e["name"] != WINDOW]
    segments = _host_segments(host, w0, w1)

    busy_total = 0.0
    module_s: Dict[str, float] = {}
    module_runs: Dict[str, int] = {}
    pallas_s: Dict[str, float] = {}
    op_s: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for chip in chips:
        mods = sorted((e["t0"], e["t0"] + e["dur"], e["name"]) for e in events
                      if e["kind"] == "module" and e["chip"] == chip
                      and w0 <= e["t0"] < w1)
        starts = [m[0] for m in mods]
        for a, b, name in mods:
            module_s[name] = module_s.get(name, 0.0) + (min(b, w1) - a) * 1e-9
            module_runs[name] = module_runs.get(name, 0) + 1
        ops = sorted((e for e in events if e["kind"] == "op" and e["chip"] == chip),
                     key=lambda e: (e["t0"], -e["dur"]))
        spans = []
        for j, e in enumerate(ops):
            a, b = max(e["t0"], w0), min(e["t0"] + e["dur"], w1)
            if b <= a:
                continue
            spans.append((a, b))
            i = bisect.bisect_right(starts, e["t0"]) - 1
            owner = mods[i][2] if i >= 0 and e["t0"] < mods[i][1] else "(no program)"
            if e["pallas"]:
                pallas_s[owner] = pallas_s.get(owner, 0.0) + (b - a) * 1e-9
            # a loop's op spans the ops of its body: count only the body
            if j + 1 < len(ops) and ops[j + 1]["t0"] < e["t0"] + e["dur"]:
                continue
            key = f"{owner}/{'pallas:' if e['pallas'] else ''}{e['name']}"
            op_s[key] = op_s.get(key, 0.0) + (b - a) * 1e-9
        busy = _union(spans)
        busy_total += sum(b - a for a, b in busy) * 1e-9
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for label, ns in _overlap(idle, segments).items():
            gaps[label] = gaps.get(label, 0.0) + ns * 1e-9 / len(chips)
    return Reduction(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total / len(chips),
        module_s=module_s,
        module_runs=module_runs,
        pallas_s=pallas_s,
        device_ops=_top(op_s),
        idle_gaps=_top(gaps),
    )


def _host_segments(host: Sequence[dict], w0: float, w1: float) -> List[tuple]:
    """Cut ``[w0, w1)`` at every host span's start and end; each piece gets
    the innermost (shortest) span that covers it, by one sweep."""
    cuts = sorted({w0, w1} | {t for e in host for t in (e["t0"], e["t0"] + e["dur"])
                              if w0 < t < w1})
    spans = sorted((e["t0"], e["t0"] + e["dur"], e["name"]) for e in host)
    out, active, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i][0] <= a:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[1] > a]
        label = min(active, key=lambda s: s[1] - s[0])[2] if active else "(no span)"
        out.append((a, b, label))
    return out


def _overlap(intervals: Sequence[tuple], segments: Sequence[tuple]) -> Dict[str, float]:
    """Length of sorted, disjoint ``intervals`` inside each label's segments."""
    out: Dict[str, float] = {}
    j = 0
    for a, b in intervals:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            sa, sb, label = segments[k]
            out[label] = out.get(label, 0.0) + min(b, sb) - max(a, sa)
            k += 1
    return out


@contextlib.contextmanager
def capture(host_names: Iterable[str], into: list):
    """Profile the block; on exit, append its plain events to ``into``.
    The trace goes to a temporary directory, which is removed."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        into.extend(events_from_xspace(paths[0], host_names))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
