"""Mean wall time of a decode step, ms: from dispatch of the compiled step
until its logits are ready on the device, as the engine then reads them."""


def read(run):
    steps = run.spans.of("decode")
    return run.spans.total("decode") / len(steps) * 1e3 if steps else None
