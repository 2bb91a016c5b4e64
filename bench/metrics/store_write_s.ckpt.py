"""Store write time per save, s: time in the checkpoint blob handle's
``write`` over the number of saves."""


def read(run):
    n = len(run.spans.of("ckpt_save"))
    calls = run.spans.of("store_write")
    return run.spans.total("store_write") / n if n and calls else None
