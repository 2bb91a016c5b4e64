"""Model FLOP utilisation of decode, %: the model FLOPs of the live rows of
every decode step in the window (``bench/flops.py``) over the device time
of the decode program in the trace times the chip's bf16 peak."""

from bench import flops


def read(run):
    rows = (run.sut.trace_shapes or {}).get("decode_rows")
    t = run.trace.program_s("_decode_impl") if run.trace else None
    if not rows or not t:
        return None
    s = flops.dense_sizes(run.cell.config)
    work = sum(flops.decode_flops(r, s) for r in rows if r)
    return 100.0 * work / (t * run.peaks["bf16_flops_per_s"])
