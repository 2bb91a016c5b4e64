"""Cluster / Session / BlobHandle API tests: snapshot pinning, the shared
cache tier and its publish-frontier gating, version-watch subscriptions, GC
coherence across session caches, and the deprecated ``BlobStore`` facade.
"""

import threading

import numpy as np
import pytest

from repro.core import BlobStore, Cluster

PAGE = 64


def make_cluster(**kw):
    kw.setdefault("n_data_providers", 4)
    kw.setdefault("n_metadata_providers", 4)
    kw.setdefault("shared_cache_bytes", 1 << 20)
    return Cluster(**kw)


def page(fill, nbytes=PAGE):
    return np.full(nbytes, fill, np.uint8)


# ------------------------------ snapshots -------------------------------------


def test_snapshot_pins_version_across_later_writes():
    cluster = make_cluster()
    handle = cluster.session().create(8 * PAGE, PAGE)
    handle.write(page(1, 8 * PAGE), 0)  # v1
    snap = handle.snapshot()
    assert snap.version == 1
    handle.write(page(2, 8 * PAGE), 0)  # v2
    handle.write(page(3, 8 * PAGE), 0)  # v3
    # the pinned view is immutable: later writes never leak in
    assert (snap.read(0, 8 * PAGE) == 1).all()
    assert (handle.read(0, PAGE).data == 3).all()
    cluster.close()


def test_snapshot_pin_survives_gc_of_other_versions():
    """GC with keep_versions NOT including the snapshot's version must still
    keep the pinned version fully readable (the pin is an implicit keep)."""
    cluster = make_cluster()
    handle = cluster.session().create(8 * PAGE, PAGE)
    handle.write(page(1, 8 * PAGE), 0)  # v1
    snap = handle.at(1)
    handle.write(page(2, 8 * PAGE), 0)  # v2 rewrites everything
    nodes, pages, _ = cluster.gc(handle.blob_id, keep_versions=[2])
    assert (nodes, pages) == (0, 0)  # v1 was pinned: nothing collectable
    assert (snap.read(0, 8 * PAGE) == 1).all()
    # releasing the pin makes v1 collectable
    snap.release()
    assert not snap.pinned
    nodes, pages, _ = cluster.gc(handle.blob_id, keep_versions=[2])
    assert pages == 8  # v1's pages die now
    with pytest.raises(KeyError):
        handle.read(0, 8 * PAGE, version=1)
    cluster.close()


def test_gc_tolerates_a_pin_on_an_already_collected_version():
    """A snapshot of a version an earlier pass collected protects nothing
    (its first read fails), but its pin must not break later GC passes."""
    cluster = make_cluster()
    handle = cluster.session().create(8 * PAGE, PAGE)
    handle.write(page(1, 8 * PAGE), 0)  # v1
    handle.write(page(2, 8 * PAGE), 0)  # v2 rewrites everything
    cluster.gc(handle.blob_id, keep_versions=[2])  # v1 collected
    snap = handle.at(1)
    handle.write(page(3, 8 * PAGE), 0)  # v3
    _, pages, frontier = cluster.gc(handle.blob_id, keep_versions=[3])
    assert (pages, frontier) == (8, 3)  # v2's pages die; v1's pin is moot
    with pytest.raises(KeyError):
        snap.read(0, 8 * PAGE)
    snap.release()
    cluster.close()


def test_snapshot_context_manager_releases_pin():
    cluster = make_cluster()
    handle = cluster.session().create(4 * PAGE, PAGE)
    handle.write(page(5, 4 * PAGE), 0)
    with handle.snapshot() as snap:
        assert cluster.pinned_versions(handle.blob_id) == {1}
        assert (snap.read(0, PAGE) == 5).all()
    assert cluster.pinned_versions(handle.blob_id) == set()
    cluster.close()


def test_snapshot_rereads_are_lock_free():
    """Repeated reads through a snapshot never consult the version manager:
    the serialized actor is paid once, at snapshot creation."""
    cluster = make_cluster()
    handle = cluster.session().create(8 * PAGE, PAGE)
    handle.write(page(1, 8 * PAGE), 0)
    vm = cluster.version_manager
    calls = []
    orig = vm.resolve_read_version
    vm.resolve_read_version = lambda *a: (calls.append(a), orig(*a))[1]
    try:
        snap = handle.snapshot()  # ONE resolve
        for _ in range(5):
            snap.readv([(0, 2 * PAGE), (4 * PAGE, PAGE)])
    finally:
        vm.resolve_read_version = orig
    assert len(calls) == 1
    cluster.close()


def test_at_rejects_unpublished_and_abandoned_versions():
    cluster = make_cluster()
    handle = cluster.session().create(4 * PAGE, PAGE)
    with pytest.raises(ValueError, match="not yet published"):
        handle.at(1)
    cluster.close()


# ---------------------------- shared cache tier -------------------------------


def test_shared_tier_hit_accounting_across_sessions():
    """Session A's cold read fills the shared tier; session B's identical
    read is pure RAM hits — per-session ledgers attribute each side, the
    cluster ledger aggregates both."""
    cluster = make_cluster()
    writer = cluster.session()
    handle = writer.create(8 * PAGE, PAGE)
    handle.write(np.arange(8 * PAGE, dtype=np.uint8), 0)

    a = cluster.session(cache_bytes=0)
    b = cluster.session(cache_bytes=0)
    cluster.stats.reset()
    a.open(handle.blob_id).read(0, 8 * PAGE)  # cold: fills the shared tier
    assert a.stats.cache_misses == 8 and a.stats.cache_hits == 0
    b.open(handle.blob_id).read(0, 8 * PAGE)  # pure shared-tier hits
    assert b.stats.cache_hits == 8 and b.stats.cache_misses == 0
    assert b.stats.data_rounds == 0  # no provider traffic at all
    # cluster ledger = sum of the sessions'
    assert cluster.stats.cache_hits == a.stats.cache_hits + b.stats.cache_hits
    assert cluster.stats.cache_misses == a.stats.cache_misses + b.stats.cache_misses
    assert b.cache_hit_rate == 1.0
    cluster.close()


def test_shared_tier_single_flight_across_sessions():
    """Concurrent cold readers in DIFFERENT sessions collapse to one provider
    fetch per page (node-wide single-flight at the shared tier)."""
    from repro.core.provider import DataProvider

    cluster = make_cluster(max_workers=32)
    writer = cluster.session(cache_bytes=0)
    handle = writer.create(16 * PAGE, PAGE)
    payload = np.arange(16 * PAGE, dtype=np.uint8) % 251
    handle.write(payload, 0)

    fetched_keys = []
    count_lock = threading.Lock()
    real_get_pages = DataProvider.get_pages

    def counting_get_pages(self, page_keys):
        with count_lock:
            fetched_keys.extend(page_keys)
        threading.Event().wait(0.05)  # keep readers genuinely overlapped
        return real_get_pages(self, page_keys)

    n_readers = 8
    barrier = threading.Barrier(n_readers)
    results = [None] * n_readers
    errors = []

    def reader(i):
        try:
            mine = cluster.session(cache_bytes=0).open(handle.blob_id)
            barrier.wait()
            results[i] = mine.read(0, 16 * PAGE, version=1).data
        except Exception as e:  # pragma: no cover
            errors.append(e)

    DataProvider.get_pages = counting_get_pages
    try:
        threads = [threading.Thread(target=reader, args=(i,)) for i in range(n_readers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        DataProvider.get_pages = real_get_pages

    assert not errors
    for r in results:
        np.testing.assert_array_equal(r, payload)
    assert len(fetched_keys) == 16  # one fetch per page for 8 sessions
    assert len(set(fetched_keys)) == 16
    cluster.close()


def test_own_unpublished_writes_hit_private_tier_only():
    """Write-through lands in the writer's PRIVATE cache under its assigned
    versions; the shared tier stays empty until a validated read fills it."""
    cluster = make_cluster()
    writer = cluster.session()
    handle = writer.create(8 * PAGE, PAGE)
    handle.write(page(1, 4 * PAGE), 0)
    assert writer.cache.cached_versions(handle.blob_id) == [1]
    assert cluster.shared_cache.cached_versions(handle.blob_id) == []
    # the writer's own re-read is RAM (private tier), no provider traffic
    cluster.stats.reset()
    handle.read(0, 4 * PAGE, version=1)
    assert cluster.stats.data_rounds == 0
    assert writer.stats.cache_hits >= 4
    cluster.close()


def test_unpublished_writes_invisible_across_sessions():
    """The acceptance invariant: a cross-session read of an unpublished
    version is impossible by construction — the read path rejects it at the
    publish frontier, and the shared tier never holds unpublished pages."""
    cluster = make_cluster()
    writer = cluster.session()
    handle = writer.create(8 * PAGE, PAGE)
    blob = handle.blob_id
    # wedge publication: v1 assigned to a writer that never reports success
    cluster.version_manager.assign_version(blob, 0, 1)
    v2 = None
    # v2's writev completes fully but cannot publish behind the v1 hole
    v2 = handle.writev([(0, page(9, 8 * PAGE))])[0]
    assert v2 == 2
    assert handle.latest_published() == 0
    # the writer holds its own pages in its private cache...
    assert writer.cache.cached_versions(blob) == [v2]
    # ...but another session can neither read the version nor find any trace
    # of it in the shared tier
    other = cluster.session().open(blob)
    with pytest.raises(ValueError, match="not yet published"):
        other.read(0, PAGE, version=v2)
    with pytest.raises(ValueError, match="not yet published"):
        other.at(v2)
    assert cluster.shared_cache.cached_versions(blob) == []
    # once the frontier advances past the hole, the same read succeeds
    cluster.version_manager.abandon(blob, [1])
    assert other.read(0, PAGE, version=v2).data[0] == 9
    cluster.close()


# ------------------------------ GC coherence ----------------------------------


def test_gc_purges_shared_tier_and_every_session_cache():
    cluster = make_cluster()
    writer = cluster.session()
    handle = writer.create(8 * PAGE, PAGE)
    handle.write(page(1, 8 * PAGE), 0)  # v1 (write-through: writer cache)
    handle.write(page(2, 8 * PAGE), 0)  # v2
    a = cluster.session()
    b = cluster.session()
    for sess in (a, b):
        h = sess.open(handle.blob_id)
        h.read(0, 8 * PAGE, version=1)  # fills shared tier + touches session
        h.read(0, 8 * PAGE, version=2)
    assert cluster.shared_cache.cached_versions(handle.blob_id) == [1, 2]
    cluster.gc(handle.blob_id, keep_versions=[2])
    assert cluster.shared_cache.cached_versions(handle.blob_id) == [2]
    for sess in (writer, a, b):
        assert 1 not in sess.cache.cached_versions(handle.blob_id)
    # v2 still fully readable everywhere
    assert (a.open(handle.blob_id).read(0, 8 * PAGE, version=2).data == 2).all()
    cluster.close()


def test_write_async_rejected_on_closed_session():
    """A closed session's writer pool is gone and GC no longer purges its
    cache — silently resurrecting the pool would leak threads."""
    cluster = make_cluster()
    sess = cluster.session()
    handle = sess.create(4 * PAGE, PAGE)
    sess.close()
    with pytest.raises(RuntimeError, match="closed session"):
        handle.write_async(page(1), 0)
    cluster.close()


def test_sessions_draw_distinct_replica_choice_streams():
    """N sessions seeded identically would sample the same replica pair at
    every draw and re-herd hot pages; the streams must diverge."""
    cluster = make_cluster()
    streams = [
        tuple(
            tuple(sess._rng.sample(range(8), 2))
            for sess in [cluster.session()]
            for _ in range(8)
        )
        for _ in range(4)
    ]
    assert len(set(streams)) == len(streams)
    cluster.close()


def test_closed_session_cache_not_purged_but_forgotten():
    cluster = make_cluster()
    sess = cluster.session()
    assert sess in cluster.sessions()
    sess.close()
    assert sess not in cluster.sessions()
    sess.close()  # idempotent
    cluster.close()


# ------------------------------ version watch ---------------------------------


def test_watch_delivers_versions_in_order_under_concurrent_publishes():
    """Wakeup ordering: N sessions publish concurrently; a watcher receives
    the dense version sequence 1..N strictly in order."""
    cluster = make_cluster(n_data_providers=8, max_workers=16)
    blob = cluster.alloc(32 * PAGE, PAGE)
    watch = cluster.session().open(blob).watch()
    n_writers = 8
    barrier = threading.Barrier(n_writers)

    def writer(i):
        h = cluster.session(cache_bytes=0).open(blob)
        barrier.wait()
        h.write(page(i + 1), (i % 32) * PAGE)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(n_writers)]
    for t in threads:
        t.start()
    delivered = [watch.next(timeout=10) for _ in range(n_writers)]
    for t in threads:
        t.join()
    assert delivered == list(range(1, n_writers + 1))  # dense AND ordered
    assert watch.next(timeout=0.05) is None  # nothing further
    cluster.close()


def test_watch_wakes_mid_wait_and_times_out_cleanly():
    cluster = make_cluster()
    handle = cluster.session().create(4 * PAGE, PAGE)
    watch = handle.watch()
    assert watch.next(timeout=0.05) is None  # nothing published yet

    def later():
        threading.Event().wait(0.1)
        handle.write(page(1), 0)

    t = threading.Thread(target=later)
    t.start()
    assert watch.next(timeout=10) == 1  # woken by the publish, not polling
    t.join()
    cluster.close()


def test_watch_skips_abandoned_holes():
    cluster = make_cluster()
    handle = cluster.session().create(8 * PAGE, PAGE)
    blob = handle.blob_id
    vm = cluster.version_manager
    vm.assign_version(blob, 0, 1)  # v1: writer will die
    v2 = None
    watch = handle.watch()
    v2 = handle.writev([(4 * PAGE, page(2))])[0]  # v2 completes, waits on v1
    vm.abandon(blob, [1])  # v1 becomes a hole; v2 publishes
    assert watch.next(timeout=5) == v2  # the hole is never delivered
    assert watch.drain() == []
    cluster.close()


def test_watch_drain_collects_backlog_without_blocking():
    cluster = make_cluster()
    handle = cluster.session().create(8 * PAGE, PAGE)
    watch = handle.watch()
    for i in range(3):
        handle.write(page(i + 1), 0)
    assert watch.drain() == [1, 2, 3]
    cluster.close()


def test_wait_for_version_blocks_until_publication():
    cluster = make_cluster()
    handle = cluster.session().create(4 * PAGE, PAGE)
    assert not handle.wait_for_version(1, timeout=0.05)

    def pub():
        handle.write(page(1), 0)

    t = threading.Thread(target=pub)
    t.start()
    assert handle.wait_for_version(1, timeout=10)
    t.join()
    cluster.close()


# ------------------------------ facade compat ---------------------------------


def test_blobstore_facade_smoke():
    """The deprecated entry points keep working, warn on construction, and
    route through the same cluster/session machinery."""
    with pytest.warns(DeprecationWarning, match="BlobStore is deprecated"):
        store = BlobStore(n_data_providers=4, n_metadata_providers=4)
    blob = store.alloc(16 * PAGE, PAGE)
    v1 = store.write(blob, page(1, 2 * PAGE), 0)
    assert v1 == 1
    res = store.read(blob, None, 0, 2 * PAGE)
    assert (res.data == 1).all() and res.latest_published == 1
    vs = store.writev(blob, [(4 * PAGE, page(2)), (8 * PAGE, page(3, 2 * PAGE))])
    assert vs == [2, 3]
    outs = store.readv(blob, None, [(4 * PAGE, PAGE), (8 * PAGE, PAGE)])
    assert outs[0][0] == 2 and outs[1][0] == 3
    fut = store.write_async(blob, page(4), 12 * PAGE)
    assert fut.result() == 4
    store.flush()
    v5 = store.write_unaligned(blob, page(5, 10), 3)
    assert store.read(blob, v5, 3, 10).data[0] == 5
    # old attribute surface still reachable
    assert store.version_manager.latest_published(blob) == v5
    assert store.page_cache is not None and store.replica_balancer is not None
    assert store.stats.data_rounds > 0
    assert store.storage_bytes() > 0
    _, pages, _ = store.gc(blob, keep_versions=[v5])
    assert pages > 0
    assert (store.read(blob, None, 0, PAGE).data[:1] == 1).all()
    store.close()


def test_facade_is_one_session_on_a_private_cluster():
    with pytest.warns(DeprecationWarning):
        store = BlobStore(n_data_providers=2, n_metadata_providers=2)
    assert store.cluster.sessions() == [store.session]
    assert store.cluster.shared_cache is None  # pre-split topology
    assert store.page_cache is store.session.cache
    store.close()
