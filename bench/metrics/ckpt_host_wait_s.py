"""Device-to-host wait per save, s: each save's ``host_wait_s`` (the
longest any one writer waited for its shards to reach the host), mean over
the saves of the window."""


def read(run):
    waits = [r["host_wait_s"] for r in run.window.get("records", ())]
    return sum(waits) / len(waits) if waits else None
