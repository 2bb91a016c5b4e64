"""Longest shard save per save, s: in each ``ckpt_save`` span, the longest
``shard_save`` span inside it (one writer's gather of its digests and its
store writes), mean over the saves. ``None`` where the checkpointer saves
no shard by shard."""


def read(run):
    longest = []
    for _, a, b, _ in run.spans.of("ckpt_save"):
        inside = [t1 - t0 for _, t0, t1, _ in run.spans.of("shard_save") if a <= t0 and t1 <= b]
        if inside:
            longest.append(max(inside))
    return sum(longest) / len(longest) if longest else None
