"""Checkpointer self time per restore, s: the ``restore`` span (until every
leaf is ready) minus the part its store ``read`` calls cover (what is
left: ``frombuffer`` copies and ``device_put``)."""


def read(run):
    n = len(run.spans.of("restore"))
    if not n or not run.spans.of("store_read"):
        return None
    return run.spans.self_time("restore", ["store_read"]) / n
