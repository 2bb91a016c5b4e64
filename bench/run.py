"""Run one cell of the chip benchmark once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; everything it
names is found by name under ``bench/`` (see ``bench/harness.py``). One
process holds the chips. It makes its inputs from ``--seed``, sets up and
warms up the system (``setup_s``), drives the measured window for
``--seconds``, then checks what the window produced against the plain
reference. With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` the profiler runs over the window and the result
carries its per-layer metrics, the device's busy time and a breakdown.

The last line of standard output is one JSON object. The numbers compared,
each beside its limit, are the last lines of standard error and the last key
of that object. With no TPU, or fewer chips than the cell asks for, the run
exits with code 3 and prints no result.

``--rate`` (a sweep for the knee) and ``--check control`` (the control put
in the program's place, which has to come out not correct) serve to define
a cell; the benchmark's own runs use neither.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)  # keep bench/trace.py from shadowing the standard library
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=0.0, help=argparse.SUPPRESS)
    ap.add_argument("--check", choices=("cell", "control"), default="cell",
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def require_chips(n: int):
    """The devices of the cell; exits 3 where JAX finds no TPU or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        print(f"bench: the cell needs {n} TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        sys.exit(3)
    return devices[:n]


def compile_counter() -> dict:
    """Counts backend compiles from here on (persistent-cache hits excluded)."""
    from jax import monitoring

    seen = {"compiles": 0}

    def listen(event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            seen["compiles"] += 1

    monitoring.register_event_duration_secs_listener(listen)
    return seen


def run_cell(args: argparse.Namespace, devices, peaks: dict, t_start: float,
             cell=None) -> dict:
    """One run of a cell; ``cell`` (a ``harness.Cell``) replaces the one
    ``BENCHMARK.json`` names, for tests at a small size."""
    import jax

    from bench import harness
    from bench import trace as btrace

    if cell is None:
        cell = harness.Cell(harness.load_json(ROOT / "BENCHMARK.json"), args.workload)
    system = cell.system()
    compiles = compile_counter()
    run = harness.Run(cell, peaks)

    sut = system.setup(cell, args.seed, args.seconds, args.rate,
                       control=args.check == "control")
    run.sut = sut
    run.setup_s = time.perf_counter() - t_start
    print(f"bench: set-up took {run.setup_s:.3f} s", file=sys.stderr, flush=True)
    spans = run.spans = harness.Spans(annotate=bool(args.trace))
    events: list = []
    before = compiles["compiles"]
    if args.trace:
        host_names = system.instrument(sut, spans)
        with btrace.capture(host_names, events):
            with jax.profiler.TraceAnnotation(btrace.WINDOW):
                run.window = system.drive(sut, args.seconds, spans)
        run.trace = btrace.reduce(events)
        del events
    else:
        run.window = system.drive(sut, args.seconds, spans)
    window_compiles = compiles["compiles"] - before
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)

    checks = system.check(sut, run.window)
    checks.append({"name": "compiles_in_window", "value": window_compiles, "limit": 0})
    correct = all(_holds(c) for c in checks)

    metrics = {}
    wanted = cell.per_layer if args.trace else cell.end_to_end
    for m in wanted:
        value = harness.metric_reader(m["name"])(run)
        if value is None:
            if not args.trace:
                raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": run.window["attempted"],
              "failed": run.window["failed"], "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]} for c in checks}
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit <= {c['limit']!r})",
              file=sys.stderr)
    return result


def _holds(c: dict) -> bool:
    return c["value"] <= c["limit"]  # NaN compares false


def main(argv=None) -> None:
    args = parse(argv)
    peaks_all = json.loads((ROOT / "bench" / "peaks.json").read_text())
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    chips = {w["name"]: int(w["chips"]) for w in bench["workloads"]}
    if args.workload not in chips:
        sys.exit(f"bench: unknown workload {args.workload!r}; known: {sorted(chips)}")
    devices = require_chips(chips[args.workload])
    if devices[0].device_kind not in peaks_all:
        sys.exit(f"bench: no peaks for device kind {devices[0].device_kind!r} in bench/peaks.json")

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    result = run_cell(args, devices, peaks_all[devices[0].device_kind], T_START)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    os._exit(0)  # the store's worker threads hold nothing that needs a clean exit


if __name__ == "__main__":
    main()
