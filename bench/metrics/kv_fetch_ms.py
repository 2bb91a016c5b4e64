"""Mean time in ``BlobKVClient.fetch_pages``, ms, per admission that
fetched shared pages from the store."""


def read(run):
    calls = run.spans.of("kv_fetch")
    return run.spans.total("kv_fetch") / len(calls) * 1e3 if calls else None
