"""The traffic generator gives every seed the same work."""

import collections

from bench import load

MIX = {"rate_per_s": 2.5, "shared_prefixes": {"count": 4, "tokens": 16},
       "tail_tokens": {"median": 12, "sigma": 0.8, "min": 8, "max": 24, "step": 8},
       "new_tokens": {"median": 4, "sigma": 0.8, "min": 2, "max": 9, "step": 1}}


def _work(s: load.Schedule):
    reqs = s.requests
    gaps = sorted(round(b.due_s - a.due_s, 9) for a, b in zip(reqs, reqs[1:]))
    return (len(reqs),
            sorted(len(r.prompt) for r in reqs),
            sorted(r.new_tokens for r in reqs),
            collections.Counter(r.prefix for r in reqs))


def test_same_work_in_the_same_order_for_every_seed():
    a = load.schedule(MIX, 100, seed=1, seconds=20)
    b = load.schedule(MIX, 100, seed=2**33 + 1, seconds=20)
    assert _work(a) == _work(b)
    shape = lambda s: [(r.due_s, len(r.prompt), r.new_tokens, r.prefix) for r in s.requests]
    assert shape(a) == shape(b)
    assert [r.prompt for r in a.requests] != [r.prompt for r in b.requests]


def test_same_seed_same_requests():
    a = load.schedule(MIX, 100, seed=7, seconds=20)
    b = load.schedule(MIX, 100, seed=7, seconds=20)
    assert [(r.due_s, r.prompt, r.new_tokens) for r in a.requests] == \
        [(r.due_s, r.prompt, r.new_tokens) for r in b.requests]


def test_schedule_shape():
    s = load.schedule(MIX, 100, seed=3, seconds=20)
    assert len(s.requests) == 50
    due = [r.due_s for r in s.requests]
    assert due[0] == 0 and due == sorted(due) and due[-1] < 20 * 1.05
    for r in s.requests:
        assert r.prompt[:16] == s.prefixes[r.prefix]
        assert len(r.prompt) - 16 in s.tails
        assert 2 <= r.new_tokens <= 9
    assert s.tails == [8, 16, 24]
    assert s.max_prompt == 40 and s.max_new == 9


def test_gaps_average_to_the_rate():
    s = load.schedule(MIX, 100, seed=5, seconds=40)
    assert 40 * 0.8 < s.requests[-1].due_s < 40


def test_lengths_follow_the_lognormal():
    dist = {"median": 1000, "sigma": 0.5, "min": 128, "max": 4096, "step": 128}
    got = load.lengths(dist, 101)
    assert list(got) == sorted(got) and got[50] == 1024  # the median, rounded to a step
    assert all(x % 128 == 0 for x in got) and got.min() >= 128 and got.max() <= 4096
    assert got.mean() > got[50]  # the long tail lies above the median
    capped = load.lengths({**dist, "max": 1024}, 101)
    assert capped.max() == 1024 and (capped == 1024).sum() == (got >= 1024).sum()
