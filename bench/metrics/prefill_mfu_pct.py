"""Model FLOP utilisation of prefill, %: the model FLOPs of the prompts
prefilled in the window (``bench/flops.py``, from shapes) over the device
time of the prefill program in the trace times the chip's bf16 peak."""

from bench import flops


def read(run):
    lens = (run.sut.trace_shapes or {}).get("prefill_lens")
    t = run.trace.program_s("_prefill_tokens_impl") if run.trace else None
    if not lens or not t:
        return None
    s = flops.dense_sizes(run.cell.config)
    work = sum(flops.prefill_flops(n, s) for n in lens)
    return 100.0 * work / (t * run.peaks["bf16_flops_per_s"])
