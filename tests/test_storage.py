"""Tests for the paged-KV allocator (prefix sharing / COW) and the
incremental blob checkpointer."""

import hashlib
import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Cluster
from repro.storage.checkpoint import BlobCheckpointer, _copy_c_order
from repro.storage.kvcache import PagedKVAllocator


def make_session(n_data_providers=4, n_metadata_providers=4):
    return Cluster(
        n_data_providers=n_data_providers,
        n_metadata_providers=n_metadata_providers,
        shared_cache_bytes=0,
    ).session()


# ------------------------------- kv allocator -------------------------------
def test_prefix_sharing_shares_full_pages():
    a = PagedKVAllocator(n_pages=64, page_tokens=4)
    p1 = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    seq1, shared1, _ = a.admit(p1)
    assert shared1 == 0
    used_before = a.used_pages()
    # same 8-token prefix -> 2 full pages shared
    seq2, shared2, _ = a.admit([1, 2, 3, 4, 5, 6, 7, 8, 42])
    assert shared2 == 8
    assert seq2.pages[:2] == seq1.pages[:2]
    assert a.used_pages() == used_before + 1  # only the fresh tail page


def test_cow_fork_on_shared_head():
    a = PagedKVAllocator(n_pages=64, page_tokens=4)
    seq1, _, _ = a.admit([1, 2, 3, 4, 5, 6, 7, 8])  # two full pages
    seq2, shared, _ = a.admit([1, 2, 3, 4, 5, 6, 7, 8])  # fully shared
    assert shared == 8
    # seq2 decodes: its head page (page index 2) is fresh -> no copy
    copies = a.append_token(seq2.seq_id)
    assert copies == []
    # rewind case: a third sequence shares, then appends into page 2 which
    # is NOT shared (fresh per admit) -> still no copy
    # force-shared head: snapshot seq1 then decode seq1 beyond its pages
    snap = a.snapshot(seq1.seq_id)
    copies = a.append_token(seq1.seq_id)  # head page 2 freshly allocated
    a.release_snapshot(snap)


def test_cow_copy_when_appending_into_shared_partial_page():
    a = PagedKVAllocator(n_pages=64, page_tokens=4)
    seq1, _, _ = a.admit([1, 2, 3, 4, 5, 6])  # page0 full, page1 partial
    # share page0 only; the diverging tail keeps page1 of seq2 fresh
    seq2, shared, _ = a.admit([1, 2, 3, 4, 9, 9])
    assert shared == 4
    # seq1's partial head page (page1) has ref 1 -> no copy on append
    assert a.append_token(seq1.seq_id) == []
    # snapshot seq1 (retains page1), now appending must COW-fork page1
    snap = a.snapshot(seq1.seq_id)
    copies = a.append_token(seq1.seq_id)
    assert len(copies) == 1
    src, dst = copies[0]
    assert src == snap.pages[1] and dst == a._seqs[seq1.seq_id].pages[1]
    a.release_snapshot(snap)


def test_partial_page_prefix_reuse_populates_cow():
    """admit's third return value (once dead code): a prompt that ENDS inside
    a page matching a live donor's partial final page comes back with a
    (src, dst) fork and the whole prompt counted shared."""
    a = PagedKVAllocator(n_pages=64, page_tokens=4)
    donor, _, _ = a.admit([1, 2, 3, 4, 5, 6, 7])  # page1 partial: (5, 6, 7)
    seq2, shared, cow = a.admit([1, 2, 3, 4, 5, 6, 7])  # identical tail
    assert shared == 7 and len(cow) == 1
    src, dst = cow[0]
    assert src == donor.pages[1] and dst == seq2.pages[1]
    assert dst != src  # a fork, not an alias: appends never hit the donor
    assert a.stats["cow_copies"] >= 1
    assert a.stats["partial_shared_tokens"] == 3
    # a shorter tail that PREFIXES a donor's also forks (stale positions
    # beyond it are masked by length and overwritten by decode)
    seq3, shared3, cow3 = a.admit([1, 2, 3, 4, 5, 6])
    assert shared3 == 6 and len(cow3) == 1
    # a diverging tail gets no reuse
    seq4, shared4, cow4 = a.admit([1, 2, 3, 4, 9, 9])
    assert shared4 == 4 and cow4 == []
    # a tail that SPANS past the donor page gets no partial reuse either
    seq5, shared5, cow5 = a.admit([1, 2, 3, 4, 5, 6, 7, 8, 9])
    assert shared5 == 4 and cow5 == []


def test_partial_donor_entry_dies_with_its_page():
    a = PagedKVAllocator(n_pages=64, page_tokens=4)
    donor, _, _ = a.admit([1, 2, 3, 4, 5, 6, 7])
    a.finish(donor.seq_id)  # frees the partial page -> donor entry must die
    seq2, shared, cow = a.admit([1, 2, 3, 4, 5, 6, 7])
    assert cow == [] and shared == 4  # only the indexed full page shares


def test_finish_releases_pages_and_index_eviction():
    a = PagedKVAllocator(n_pages=8, page_tokens=4)
    seqs = []
    for i in range(3):
        s, _, _ = a.admit([i * 10 + 1, i * 10 + 2, i * 10 + 3, i * 10 + 4])
        seqs.append(s)
    for s in seqs:
        a.finish(s.seq_id)
    # pages remain in the prefix index (cache) but are evictable: admitting
    # new sequences must succeed by evicting cache pages
    for i in range(4):
        a.admit([100 + i, 200 + i, 300 + i, 400 + i, 500 + i])
    assert a.used_pages() <= 8


def test_snapshot_isolation_under_decode():
    """The paper's read/write concurrency: a snapshot's pages survive the
    writer's continued decoding (ref'd), and release frees them."""
    a = PagedKVAllocator(n_pages=16, page_tokens=2)
    seq, _, _ = a.admit([1, 2, 3])
    snap = a.snapshot(seq.seq_id)
    for _ in range(6):
        a.append_token(seq.seq_id)
    assert all(a._ref.get(p, 0) >= 1 for p in snap.pages)
    a.release_snapshot(snap)
    a.finish(seq.seq_id)
    assert a.used_pages() <= len(a._prefix_index) + 1


# ------------------------------- checkpointer -------------------------------
def _tiny_state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "w1": jax.random.normal(k, (64, 64), jnp.float32),
        "w2": jnp.zeros((32,), jnp.float32),
        "step": jnp.array(0, jnp.int32),
    }


def test_checkpoint_roundtrip():
    session = make_session()
    state = _tiny_state()
    ck = BlobCheckpointer(session, state, page_size=4096)
    rec = ck.save(0, state)
    assert rec.dirty_pages > 0
    out = ck.restore(0)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_incremental_checkpoint_writes_only_dirty_pages():
    session = make_session()
    state = _tiny_state()
    ck = BlobCheckpointer(session, state, page_size=4096)
    r0 = ck.save(0, state)
    # identical state -> zero dirty pages (pure COW sharing)
    r1 = ck.save(1, state)
    assert r1.dirty_pages == 0
    # touch one leaf -> only its page(s) rewritten
    state2 = dict(state, w2=state["w2"] + 1.0)
    r2 = ck.save(2, state2)
    assert 0 < r2.dirty_pages < r0.dirty_pages
    # all three checkpoints readable
    w2_old = ck.restore(1)["w2"]
    w2_new = ck.restore(2)["w2"]
    assert float(w2_old[0]) + 1.0 == float(w2_new[0])


def test_checkpoint_crash_consistency():
    """A checkpoint is visible only after completion: reading while a save is
    'in flight' (simulated by unpublished writes) yields the previous one."""
    session = make_session()
    state = _tiny_state()
    ck = BlobCheckpointer(session, state, page_size=4096)
    ck.save(0, state)
    before = ck.restore(0)
    # simulate concurrent reader during a save of new state
    state2 = jax.tree.map(lambda x: x + 1 if x.dtype != jnp.int32 else x, state)
    t = ck.save_async(1, state2)
    got = ck.restore(0)  # reader pinned to step 0 stays consistent
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(before)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    t.join()
    after = ck.restore(1)
    np.testing.assert_array_equal(np.asarray(after["w1"]), np.asarray(state2["w1"]))


def test_checkpoint_gc_retention():
    session = make_session()
    state = _tiny_state()
    ck = BlobCheckpointer(session, state, page_size=4096, keep_last=2)
    for i in range(5):
        state = dict(state, w1=state["w1"] + 1.0)
        ck.save(i, state)
    assert len(ck.checkpoints) == 2
    ck.restore(ck.checkpoints[0].step)
    ck.restore(ck.checkpoints[1].step)


def test_checkpoint_reshard_restore():
    """Elastic restart: restore with explicit shardings onto a CPU mesh."""
    session = make_session(2, 2)
    state = _tiny_state()
    ck = BlobCheckpointer(session, state, page_size=4096)
    ck.save(0, state)
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
    out = ck.restore(0, shardings=sh)
    assert all(x.sharding == NamedSharding(mesh, P()) for x in jax.tree.leaves(out))

# ---------------------------- the streamed save ----------------------------
def _strided(shape=(23, 100, 1001), perm=(1, 0, 2)):
    """A NumPy leaf that is not C-contiguous, as a device array can come back
    in its device layout: the axes of a C-order array permuted."""
    base = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
    return base.transpose(perm)


def _odd_state():
    """Leaves whose sizes are not page multiples, a 0-d one and NumPy ones
    among them; ``big`` and ``strided`` (not C-contiguous) span two save tasks
    at either page size."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    return {
        "big": jax.random.normal(k1, (2_200_001,), jnp.float32),
        "mat": jax.random.randint(k2, (37, 29), -1000, 1000, jnp.int32),
        "np": np.linspace(-1.0, 1.0, 1001, dtype=np.float32),
        "scalar": jnp.array(3.5, jnp.float32),
        "strided": _strided(),
        "tail": jnp.arange(5, dtype=jnp.int32),
    }


def _serial_page_hashes(ck, state):
    """The save's digests, computed plainly: each leaf's bytes cut into pages,
    the last zero-padded, blake2b with 16-byte digests."""
    ps = ck.page_size
    out = {}
    for info, leaf in zip(ck.layout, jax.tree.leaves(state)):
        raw = np.asarray(leaf).tobytes()
        for p in range(-(-len(raw) // ps)):
            page = raw[p * ps : (p + 1) * ps].ljust(ps, b"\0")
            out[info.offset // ps + p] = hashlib.blake2b(page, digest_size=16).digest()
    return out


def _assert_restores(ck, step, state):
    out = ck.restore(step)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(state)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


def _count_writes(ck):
    """Wrap the handle's ``write`` on the instance, as the benchmark's span
    does; returns the list of (offset, bytes) of every call."""
    calls = []
    write = ck.handle.write

    def counted(buf, offset):
        calls.append((offset, buf.nbytes))
        return write(buf, offset)

    ck.handle.write = counted
    return calls


@pytest.mark.parametrize("page_size", [4096, 65536])
def test_streamed_save_matches_serial_reference(page_size, monkeypatch):
    # a fresh np.empty may hand back used memory; make every uint8 one do so,
    # so that padding the save leaves unzeroed shows in the stored bytes
    empty = np.empty

    def used_empty(*args, **kwargs):
        out = empty(*args, **kwargs)
        if out.dtype == np.uint8:
            out.fill(0xAB)
        return out

    monkeypatch.setattr(np, "empty", used_empty)
    state = _odd_state()
    ck = BlobCheckpointer(make_session(), state, page_size=page_size)
    calls = _count_writes(ck)
    rec = ck.save(0, state)
    ref = _serial_page_hashes(ck, state)
    assert ck._page_hash == ref
    assert rec.dirty_pages == rec.total_pages == len(ref)
    # every page dirty: one run, so one write, per leaf
    assert [off for off, _ in calls] == [info.offset for info in ck.layout]
    _assert_restores(ck, 0, state)
    # stored bytes: each leaf's, its last page zero-padded
    for info, leaf in zip(ck.layout, jax.tree.leaves(state)):
        span = -(-info.size // page_size) * page_size
        stored = ck.handle.read(info.offset, span, version=rec.version).data.tobytes()
        assert stored == np.asarray(leaf).tobytes().ljust(span, b"\0")
    # the same state again: nothing dirty, nothing written
    rec = ck.save(1, state)
    assert (rec.dirty_pages, rec.total_pages) == (0, len(ref))
    assert len(calls) == len(ck.layout)
    _assert_restores(ck, 1, state)


@pytest.mark.parametrize("page_size", [4096, 65536])
def test_streamed_save_writes_one_call_per_dirty_run(page_size):
    state = _odd_state()
    ck = BlobCheckpointer(make_session(), state, page_size=page_size)
    ck.save(0, state)
    calls = _count_writes(ck)
    per_page = page_size // 4
    big = np.array(state["big"])
    for p in (1, 3, 5, 6):  # runs: [1], [3], [5, 6]
        big[p * per_page] += 1.0
    mat = np.array(state["mat"])
    mat[0, 0] += 1
    strided = state["strided"].copy(order="K")  # keeps the permuted layout
    for p in (0, 2):  # the C-order element that starts page p: runs [0], [2]
        strided[np.unravel_index(p * per_page, strided.shape)] += 1.0
    state2 = dict(state, big=jnp.asarray(big), mat=jnp.asarray(mat), strided=strided)
    rec = ck.save(1, state2)
    assert ck._page_hash == _serial_page_hashes(ck, state2)
    first = {info.path: info.offset for info in ck.layout}
    b, m, s = first["['big']"], first["['mat']"], first["['strided']"]
    assert calls == [
        (b + page_size, page_size),
        (b + 3 * page_size, page_size),
        (b + 5 * page_size, 2 * page_size),
        (m, page_size),
        (s, page_size),
        (s + 2 * page_size, page_size),
    ]
    assert rec.dirty_pages == 7
    _assert_restores(ck, 0, state)
    _assert_restores(ck, 1, state2)


def _root(arr):
    while isinstance(arr.base, np.ndarray):
        arr = arr.base
    return arr


@pytest.mark.parametrize("page_size", [4096, 65536])
def test_partial_save_hands_the_store_only_the_dirty_bytes(page_size):
    """The store keeps what a save hands it while the pages are current: a
    partial save must not hand it views that keep a whole staging buffer
    alive. One page of a C-contiguous leaf and one of a permuted leaf (every
    page of which is staged for hashing) are dirty."""
    state = _odd_state()
    ck = BlobCheckpointer(make_session(), state, page_size=page_size)
    ck.save(0, state)
    given = []
    write = ck.handle.write

    def keep(buf, offset):
        given.append(buf)
        return write(buf, offset)

    ck.handle.write = keep
    providers = list(ck.session.cluster.provider_manager._providers.values())
    before = {(p.provider_id, key) for p in providers for key in p._pages}
    big = np.array(state["big"])
    big[page_size // 4] += 1.0
    strided = state["strided"].copy(order="K")
    strided[np.unravel_index(0, strided.shape)] += 1.0
    state2 = dict(state, big=jnp.asarray(big), strided=strided)
    rec = ck.save(1, state2)
    assert rec.dirty_pages == 2 and len(given) == 2
    stored = [page for p in providers for key, page in p._pages.items()
              if (p.provider_id, key) not in before]
    assert len(stored) >= 2
    for page in given + stored:
        assert _root(page).nbytes <= page_size
    _assert_restores(ck, 0, state)
    _assert_restores(ck, 1, state2)


def test_streamed_save_never_aliases_caller_arrays():
    x = np.arange(3000, dtype=np.float32)
    y = np.full((17, 3), 2.0, np.float64)
    ck = BlobCheckpointer(make_session(), {"x": x, "y": y}, page_size=4096)
    ck.save(0, {"x": x, "y": y})
    saved = {"x": x.copy(), "y": y.copy()}
    assert x.flags.writeable and y.flags.writeable
    x[:] = -1.0
    y[0, 0] = 7.0
    _assert_restores(ck, 0, saved)
    ck.save(1, {"x": x, "y": y})
    _assert_restores(ck, 1, {"x": x, "y": y})
    _assert_restores(ck, 0, saved)


def test_streamed_save_records_its_workers():
    one = {"w": jnp.ones((100,), jnp.float32)}
    ck = BlobCheckpointer(make_session(), one, page_size=4096)
    rec = ck.save(0, one)
    assert rec.workers == 1 and rec.host_wait_s >= 0
    assert json.loads(ck.manifest())["checkpoints"][0]["workers"] == 1
    if len(os.sched_getaffinity(0)) == 1:
        pytest.skip("one core: the pool has one thread")
    many = {f"w{i}": jnp.full((100,), float(i), jnp.float32) for i in range(8)}
    ck = BlobCheckpointer(make_session(), many, page_size=4096)
    rec = ck.save(0, many)
    assert rec.workers > 1 and rec.host_wait_s >= 0
    _assert_restores(ck, 0, many)


def test_streamed_save_refuses_a_resized_leaf():
    state = _tiny_state()
    ck = BlobCheckpointer(make_session(), state, page_size=4096)
    ck.save(0, state)
    hashes, version = dict(ck._page_hash), ck.handle.latest_published()
    with pytest.raises(ValueError, match="w2"):
        ck.save(1, dict(state, w2=jnp.zeros((33,), jnp.float32)))
    assert ck._page_hash == hashes and ck.handle.latest_published() == version
    assert [c.step for c in ck.checkpoints] == [0]


@pytest.mark.parametrize(
    "shape,perm",
    [((5, 7, 9), (1, 0, 2)), ((4, 3, 6, 5), (0, 1, 3, 2)), ((6, 8), (1, 0)),
     ((13,), (0,)), ((3, 1, 4), (2, 1, 0))],
)
def test_copy_c_order_copies_exactly_the_range(shape, perm):
    rng = np.random.default_rng(len(shape))
    src = rng.standard_normal(shape).astype(np.float32).transpose(perm)
    ref = src.reshape(-1)  # a C-order copy
    for start, stop in [(0, src.size), (0, 0), (src.size, src.size)] + [
        tuple(sorted(rng.integers(0, src.size + 1, 2))) for _ in range(100)
    ]:
        dst = np.full(src.size, np.nan, np.float32)
        _copy_c_order(dst, src, start, stop)
        np.testing.assert_array_equal(dst[start:stop], ref[start:stop])
        assert np.isnan(dst[:start]).all() and np.isnan(dst[stop:]).all()


def test_streamed_save_under_thread_stress(monkeypatch):
    """More save threads than cores and a short switch interval: every
    save still matches the serial reference and restores exactly."""
    import repro.storage.checkpoint as ckpt_mod

    monkeypatch.setattr(ckpt_mod.os, "sched_getaffinity", lambda pid: set(range(64)))
    rng = np.random.default_rng(3)
    state = {f"l{i:02d}": rng.standard_normal(3000 + 17 * i).astype(np.float32) for i in range(24)}
    state["strided"] = _strided((7, 300, 9), (1, 2, 0))
    ck = BlobCheckpointer(make_session(), state, page_size=4096)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for step in range(4):
            rec = ck.save(step, state)
            assert rec.workers == len(ck.layout)
            assert ck._page_hash == _serial_page_hashes(ck, state)
            _assert_restores(ck, step, state)
            state = {k: (v + 1 if rng.random() < 0.5 else v) for k, v in state.items()}
    finally:
        sys.setswitchinterval(interval)
