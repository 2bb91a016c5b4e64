"""Checkpointer self time per save, s: the ``save`` span minus the part its
store ``write`` calls cover (what is left: ``device_get``, ``tobytes``,
page hashing, retention)."""


def read(run):
    n = len(run.spans.of("ckpt_save"))
    if not n or not run.spans.of("store_write"):
        return None
    return run.spans.self_time("ckpt_save", ["store_write"]) / n
