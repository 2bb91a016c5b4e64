"""Roofline share of the paged decode-attention kernel, %: the least time
its calls need (each layer reads the K and V of every token the live rows
hold, against the chip's peaks) over the device time of the Pallas calls
inside the decode program, which holds only paged attention."""

from bench import flops


def read(run):
    rows = (run.sut.trace_shapes or {}).get("decode_rows")
    t = run.trace.kernel_s("_decode_impl") if run.trace else None
    if not rows or not t:
        return None
    s = flops.dense_sizes(run.cell.config)
    need = sum(s["L"] * flops.least_time(*flops.paged_cost(r, s), run.peaks) for r in rows if r)
    return 100.0 * need / t
