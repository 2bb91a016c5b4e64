"""Longest shard restore per restore, s: in each ``restore`` span, the
longest ``shard_restore`` span inside it (one device's reads of its shards
and their puts onto the device), mean over the restores. ``None`` where the
checkpointer restores no shard by shard."""


def read(run):
    longest = []
    for _, a, b, _ in run.spans.of("restore"):
        inside = [t1 - t0 for _, t0, t1, _ in run.spans.of("shard_restore") if a <= t0 and t1 <= b]
        if inside:
            longest.append(max(inside))
    return sum(longest) / len(longest) if longest else None
