"""Mean host time per admitted request, ms: time in the engine's admission
(prefix lookup, fetch and scatter of shared pages, prefill, scatter of new
pages, publish, first sample) over the number of admissions."""


def read(run):
    n = len(run.spans.of("kv_admit"))
    return run.spans.total("admit") / n * 1e3 if n else None
