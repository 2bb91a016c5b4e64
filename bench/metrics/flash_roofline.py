"""Roofline share of the flash-attention kernel, %: the least time its calls
need (each layer's causal attention over the prompt, ops and bytes from
shapes, against the chip's peaks) over the device time of the Pallas calls
inside the prefill program. Both kernels are named ``_kernel``; the prefill
program holds only flash attention."""

from bench import flops


def read(run):
    lens = (run.sut.trace_shapes or {}).get("prefill_lens")
    t = run.trace.kernel_s("_prefill_tokens_impl") if run.trace else None
    if not lens or not t:
        return None
    s = flops.dense_sizes(run.cell.config)
    need = sum(s["L"] * flops.least_time(*flops.flash_cost(n, s), run.peaks) for n in lens)
    return 100.0 * need / t
