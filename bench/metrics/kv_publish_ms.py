"""Mean time in ``BlobKVClient.publish_prompt``, ms, per admission: one
``writev`` of the new prompt pages, the wait for publication, pins and
directory registration."""


def read(run):
    calls = run.spans.of("kv_publish")
    return run.spans.total("kv_publish") / len(calls) * 1e3 if calls else None
