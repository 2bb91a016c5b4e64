"""Store read time per restore, s: time in the checkpoint blob handle's
``read`` over the number of restores."""


def read(run):
    n = len(run.spans.of("restore"))
    calls = run.spans.of("store_read")
    return run.spans.total("store_read") / n if n and calls else None
