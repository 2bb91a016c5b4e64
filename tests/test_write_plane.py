"""Pipelined asynchronous write plane + zero-copy transport tests.

Covers the overlapped ``writev`` pipeline (data puts in flight while versions
are assigned and metadata is woven), the bounded ``write_async``/``flush``
window, zero-copy page hand-off on both the write and read paths, write-through
caching, failure cleanup (placement release + orphan deletion + version
abandonment), and the per-destination write-byte accounting.
"""

import threading

import numpy as np
import pytest

from repro.core import Cluster, ProviderFailed, VersionManager
from repro.core.provider import DataProvider

PAGE = 64


def make_session(**kw):
    session_kw = {
        k: kw.pop(k)
        for k in ("cache_bytes", "replica_spread", "sync_write", "max_inflight_writes")
        if k in kw
    }
    kw.setdefault("n_data_providers", 4)
    kw.setdefault("n_metadata_providers", 4)
    kw.setdefault("shared_cache_bytes", 0)
    return Cluster(**kw).session(**session_kw)


def page(fill, nbytes=PAGE):
    return np.full(nbytes, fill, np.uint8)


# ------------------------------ zero-copy -------------------------------------


def test_writev_stores_zero_copy_views_and_freezes_source():
    """No per-page ``.copy()`` on the hot path: providers hold views of the
    writer's buffer, and the buffer is frozen so they can never change."""
    sess = make_session(n_data_providers=1, cache_bytes=0)
    handle = sess.create(8 * PAGE, PAGE)
    buf = np.arange(4 * PAGE, dtype=np.uint8)
    handle.write(buf, 0)
    provider = sess.cluster.provider_manager.get_provider(0)
    stored = [provider.get_page(k) for k in range(4)]
    for pg in stored:
        assert np.shares_memory(pg, buf)  # view, not copy
        assert not pg.flags.writeable
    with pytest.raises(ValueError):
        buf[0] = 99  # the source was surrendered to the store
    sess.cluster.close()


def test_writev_copies_unfreezable_views_once():
    """A view of a larger writable array cannot be protected by freezing
    (writes through the base would mutate the stored pages), so the write
    plane must fall back to a bulk copy — published data stays immutable."""
    sess = make_session(n_data_providers=1)
    handle = sess.create(8 * PAGE, PAGE)
    big = np.zeros(2 * PAGE, np.uint8)
    v = handle.write(big[:PAGE], 0)
    big[0] = 99  # caller mutates the base AFTER publication
    assert handle.read(0, PAGE, version=v).data[0] == 0  # snapshot unharmed
    sess.cluster.close()


def test_buffer_surrender_semantics_on_failure():
    """Validation errors must not freeze anything (no side effects before
    the patch list checks out); once the pipeline launches, the buffer is
    surrendered for good even if the write fails — another overlapping
    write may already hold zero-copy views of the same memory, so an abort
    cannot safely hand writability back."""
    sess = make_session(cache_bytes=0)
    handle = sess.create(8 * PAGE, PAGE)
    buf = np.zeros(4 * PAGE, np.uint8)
    with pytest.raises(ValueError, match="page-aligned"):
        handle.writev([(0, buf), (3, page(1))])
    buf[0] = 1  # a rejected batch froze nothing
    for pid in range(4):
        sess.cluster.provider_manager.fail_provider(pid)
    with pytest.raises(ProviderFailed):
        handle.write(buf, 0)
    assert not buf.flags.writeable  # launched pipeline -> surrendered
    sess.cluster.close()


def test_abort_leaks_hole_version_wreckage_for_later_readers():
    """When a failed writer's version becomes a publication HOLE (a
    concurrent writer was assigned after it), the abort must NOT scrub its
    stored metadata/pages: the later writer's published tree border-links
    into them."""
    sess = make_session(n_data_providers=2, cache_bytes=0)
    cluster = sess.cluster
    cluster.provider_manager.on_dead = None  # scrubbing is RepairService's job
    handle = sess.create(8 * PAGE, PAGE)
    blob = handle.blob_id
    started, release = _blocking_provider(cluster, 0)
    failed = []

    def writer_a():
        try:
            handle.write(page(1), 0)  # page 0 -> provider 0 (blocked)
        except ProviderFailed as err:
            failed.append(err)

    t = threading.Thread(target=writer_a)
    t.start()
    assert started.wait(10)
    for _ in range(200):  # wait until A holds v1
        if cluster.version_manager.assigned_versions(blob) == 1:
            break
        threading.Event().wait(0.01)
    # B runs in its own session, assigned after A
    v2 = cluster.session().open(blob).write(page(2), PAGE)
    assert v2 == 2
    # EVERY provider fails: A's mid-flight re-placement (which would
    # otherwise rescue the write onto provider 1) has no target -> abort
    for pid in (0, 1):
        cluster.provider_manager.fail_provider(pid)
    release.set()
    t.join(10)
    assert failed  # A's data put raised and its writev aborted
    # v1 is a hole: publication passed it, B's version is readable
    assert cluster.version_manager.latest_published(blob) == 2
    # A's metadata (stored mid-pipeline) survives the abort — B's tree
    # border-links into version 1 for the untouched ranges
    from repro.core import NodeKey
    leaked = dict(cluster.metadata.iter_nodes(blob))
    assert NodeKey(blob, 1, 0, 1) in leaked
    # B's own data is readable once its provider rejoins; A's page is
    # genuinely lost (never stored), which is writer-recovery territory —
    # but the metadata spine is intact
    cluster.provider_manager.recover_provider(1)
    np.testing.assert_array_equal(
        handle.read(PAGE, PAGE, version=v2).data, page(2)
    )
    cluster.close()


def test_sync_write_baseline_copies_pages():
    """The pre-pipeline A/B baseline keeps its defensive per-page copies."""
    sess = make_session(n_data_providers=1, cache_bytes=0, sync_write=True)
    handle = sess.create(8 * PAGE, PAGE)
    buf = np.arange(2 * PAGE, dtype=np.uint8)
    handle.write(buf, 0)
    provider = sess.cluster.provider_manager.get_provider(0)
    assert not any(np.shares_memory(provider.get_page(k), buf) for k in range(2))
    sess.cluster.close()


def test_full_page_read_is_zero_copy_view():
    """A read of exactly one whole page returns the stored/cached page itself
    (read-only), not a per-page Python assembly into a fresh buffer."""
    sess = make_session()
    handle = sess.create(8 * PAGE, PAGE)
    handle.write(np.arange(8 * PAGE, dtype=np.uint8), 0)
    a = handle.read(2 * PAGE, PAGE).data
    b = handle.read(2 * PAGE, PAGE).data
    assert np.shares_memory(a, b)  # both are views of the same cached page
    assert not a.flags.writeable
    # unaligned / multi-page segments still assemble into a fresh buffer
    c = handle.read(2 * PAGE + 1, PAGE).data
    assert not np.shares_memory(a, c)
    sess.cluster.close()


def test_full_page_read_of_zero_page_shares_the_zero_buffer():
    sess = make_session()
    handle = sess.create(8 * PAGE, PAGE)
    a = handle.read(0, PAGE).data
    b = handle.read(PAGE, PAGE).data
    assert np.shares_memory(a, b)  # one shared immutable zero page
    assert not a.any()


# --------------------------- write-through cache ------------------------------


def test_write_through_makes_own_rereads_free():
    sess = make_session()
    handle = sess.create(8 * PAGE, PAGE)
    v = handle.write(np.arange(4 * PAGE, dtype=np.uint8), 0)
    stats = sess.cluster.stats
    stats.reset()
    got = handle.read(0, 4 * PAGE, version=v).data
    np.testing.assert_array_equal(got, np.arange(4 * PAGE, dtype=np.uint8))
    assert stats.data_rounds == 0  # no provider round-trips
    assert stats.metadata_rounds == 0  # no tree traversal either
    assert stats.cache_hits == 4
    sess.cluster.close()


# ----------------------------- pipelining -------------------------------------


def _blocking_provider(cluster, pid):
    """Make provider ``pid``'s put_pages block until released; returns
    (started, release) events."""
    provider = cluster.provider_manager.get_provider(pid)
    started, release = threading.Event(), threading.Event()
    real_put = provider.put_pages

    def blocked_put(items):
        started.set()
        assert release.wait(10), "test released too late"
        return real_put(items)

    provider.put_pages = blocked_put
    return started, release


def test_pipelined_writev_overlaps_version_and_metadata_with_data_puts():
    """The tentpole property, asserted structurally: while the data puts are
    still in flight, the version is already assigned AND the metadata nodes
    are already stored. Only report_success waits for the join."""
    sess = make_session(n_data_providers=1, cache_bytes=0)
    cluster = sess.cluster
    handle = sess.create(8 * PAGE, PAGE)
    blob = handle.blob_id
    started, release = _blocking_provider(cluster, 0)
    done = []
    t = threading.Thread(
        target=lambda: done.append(handle.write(page(7, 2 * PAGE), 0))
    )
    t.start()
    try:
        assert started.wait(10)
        # data put is blocked right now, yet the pipeline has moved on:
        vm = cluster.version_manager
        deadline = threading.Event()
        for _ in range(200):
            if vm.assigned_versions(blob) == 1 and cluster.metadata.total_nodes() > 0:
                break
            deadline.wait(0.01)
        assert vm.assigned_versions(blob) == 1  # version assigned mid-put
        assert cluster.metadata.total_nodes() > 0  # metadata stored mid-put
        assert vm.latest_published(blob) == 0  # but success awaits the join
    finally:
        release.set()
        t.join()
    assert done == [1]
    assert cluster.version_manager.latest_published(blob) == 1
    cluster.close()


def test_sync_write_keeps_the_stage_barrier():
    """A/B contrast: with sync_write=True no version is assigned until the
    data puts complete (the pre-pipeline full barrier)."""
    sess = make_session(n_data_providers=1, cache_bytes=0, sync_write=True)
    cluster = sess.cluster
    handle = sess.create(8 * PAGE, PAGE)
    blob = handle.blob_id
    started, release = _blocking_provider(cluster, 0)
    t = threading.Thread(target=lambda: handle.write(page(7), 0))
    t.start()
    try:
        assert started.wait(10)
        threading.Event().wait(0.05)  # give a broken pipeline time to leak
        assert cluster.version_manager.assigned_versions(blob) == 0
        assert cluster.metadata.total_nodes() == 0
    finally:
        release.set()
        t.join()
    assert cluster.version_manager.latest_published(blob) == 1
    cluster.close()


# ------------------------- write_async / flush --------------------------------


def test_write_async_window_applies_backpressure():
    sess = make_session(n_data_providers=1, cache_bytes=0, max_inflight_writes=2)
    handle = sess.create(16 * PAGE, PAGE)
    started, release = _blocking_provider(sess.cluster, 0)
    f1 = handle.write_async(page(1), 0)
    f2 = handle.write_async(page(2), PAGE)  # window now full
    assert started.wait(10)
    third_submitted = threading.Event()

    def third():
        handle.write_async(page(3), 2 * PAGE)
        third_submitted.set()

    t = threading.Thread(target=third)
    t.start()
    assert not third_submitted.wait(0.1)  # blocked on the window
    release.set()
    t.join(10)
    assert third_submitted.is_set()
    assert sorted([f1.result(), f2.result()]) == [1, 2]
    flushed = sess.flush()  # completed-and-pruned writes are not re-reported
    assert 3 in flushed and set(flushed) <= {1, 2, 3}
    assert handle.latest_published() == 3
    sess.cluster.close()


def test_write_async_publishes_in_assignment_order_under_random_service():
    """Satellite: versions publish in assignment order per blob even when
    later writes' data lands first (randomized provider service times),
    across multiple concurrently streaming SESSIONS."""
    cluster = Cluster(
        n_data_providers=6, n_metadata_providers=6, max_workers=24,
        shared_cache_bytes=0,
    )
    rng = np.random.default_rng(7)
    for provider in cluster.provider_manager.providers():
        provider.page_service_seconds = float(rng.uniform(0.0, 0.004))
    blob = cluster.alloc(64 * PAGE, PAGE)
    n_writers, writes_each = 3, 8
    log_lock = threading.Lock()
    by_version = {}
    errors = []

    def writer(wid):
        try:
            handle = cluster.session(
                cache_bytes=0, max_inflight_writes=6
            ).open(blob)
            futures = []
            for i in range(writes_each):
                off = ((wid * writes_each + i) % 64) * PAGE
                fill = wid * writes_each + i + 1
                fut = handle.write_async(page(fill), off)
                futures.append((off, fill, fut))
            for off, fill, fut in futures:
                with log_lock:
                    by_version[fut.result()] = (off, fill)
        except Exception as err:  # pragma: no cover
            errors.append(err)

    threads = [threading.Thread(target=writer, args=(w,)) for w in range(n_writers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    total = n_writers * writes_each
    assert sorted(by_version) == list(range(1, total + 1))  # dense versions
    reader = cluster.session().open(blob)
    assert reader.latest_published() == total
    # every published version equals the prefix-application of patches in
    # version order (global serializability across interleaved async streams)
    oracle = np.zeros(64 * PAGE, np.uint8)
    for v in range(1, total + 1):
        off, fill = by_version[v]
        oracle[off : off + PAGE] = fill
        got = reader.read(0, 64 * PAGE, version=v).data
        np.testing.assert_array_equal(got, oracle, err_msg=f"version {v}")
    cluster.close()


def test_flush_surfaces_async_write_failure():
    sess = make_session(n_data_providers=1, cache_bytes=0)
    handle = sess.create(8 * PAGE, PAGE)
    sess.cluster.provider_manager.fail_provider(0)
    handle.write_async(page(1), 0)
    with pytest.raises(ProviderFailed):
        sess.flush()
    sess.cluster.close()


# ------------------------- failure cleanup ------------------------------------


def test_failed_writev_releases_placements_and_deletes_orphans():
    """Satellite: a mid-writev provider failure with no healthy provider
    left to re-place onto must not leak load credits, stored pages, or
    metadata nodes — and must not wedge publication."""
    # replication 2 over 2 providers: every page holds a ref on BOTH, so
    # when provider 0 dies mid-flight the re-placement has no target left
    sess = make_session(n_data_providers=2, page_replication=2, cache_bytes=0)
    cluster = sess.cluster
    cluster.provider_manager.on_dead = None  # keep the abort path isolated
    handle = sess.create(16 * PAGE, PAGE)
    baseline_load = cluster.provider_manager.load_snapshot()
    provider = cluster.provider_manager.get_provider(0)
    real_put = provider.put_pages
    dropping = [True]

    def crashed_put(items):
        if dropping[0]:
            raise ProviderFailed("injected: provider crashed mid-writev")
        return real_put(items)

    provider.put_pages = crashed_put
    with pytest.raises(ProviderFailed):
        # every retry fails, the health machine declares provider 0 dead,
        # and the mid-flight re-placement finds no healthy non-holder
        handle.write(page(1, 8 * PAGE), 0)
    assert cluster.provider_manager.dead_providers() == [0]
    # placement credits returned
    assert cluster.provider_manager.load_snapshot() == baseline_load
    # orphaned pages deleted from the live providers
    assert all(
        p.n_pages == 0
        for p in cluster.provider_manager.providers()
        if not p.failed
    )
    # metadata nodes of the doomed version dropped
    assert cluster.metadata.total_nodes() == 0
    # the assigned version was withdrawn as a hole: nothing wedges, and the
    # number is never reused
    assert cluster.version_manager.assigned_versions(handle.blob_id) == 1
    assert cluster.version_manager.is_aborted(handle.blob_id, 1)
    dropping[0] = False
    cluster.provider_manager.recover_provider(0)  # rejoin: live + placeable
    v = handle.write(page(2, 4 * PAGE), 0)
    assert v == 2
    assert handle.latest_published() == 2
    np.testing.assert_array_equal(
        handle.read(0, 4 * PAGE).data, page(2, 4 * PAGE)
    )
    cluster.close()


def test_abandon_hole_is_skipped_and_rejected():
    """A non-tail abandoned version becomes a publication hole: later
    versions publish over it, readers of it are rejected."""
    vm = VersionManager()
    blob = vm.alloc(8, PAGE)
    v1, _ = vm.assign_version(blob, 0, 2)
    v2, _ = vm.assign_version(blob, 4, 2)  # concurrent writer landed after
    vm.abandon(blob, [v1])  # v1's writer died -> hole, v2 still weaves to it
    assert vm.report_success(blob, v2) == 2  # publication passed the hole
    assert vm.latest_published(blob) == 2
    with pytest.raises(ValueError, match="abandoned"):
        vm.resolve_read_version(blob, v1)
    # the tail case is a hole too: its number is never reused, so a late
    # report from its (possibly live) writer cannot land on a new owner
    v3, _ = vm.assign_version(blob, 0, 1)
    vm.abandon(blob, [v3])
    assert vm.assigned_versions(blob) == 3
    with pytest.raises(ValueError, match="abandoned"):
        vm.resolve_read_version(blob, v3)
    v4, _ = vm.assign_version(blob, 2, 1)
    assert v4 == v3 + 1


def test_recover_replays_abandon_entries():
    vm = VersionManager()
    blob = vm.alloc(8, PAGE)
    vm.assign_version(blob, 0, 2)  # v1
    vm.assign_version(blob, 4, 2)  # v2
    vm.abandon(blob, [1])  # hole
    vm.report_success(blob, 2)
    vm2, orphans = VersionManager.recover(vm.journal)
    assert vm2.latest_published(blob) == 2
    assert orphans[blob] == []  # the abandoned version is resolved, not orphaned
    with pytest.raises(ValueError, match="abandoned"):
        vm2.resolve_read_version(blob, 1)


# ------------------------- write traffic accounting ---------------------------


def test_per_destination_write_bytes_recorded():
    sess = make_session(cache_bytes=0)
    handle = sess.create(16 * PAGE, PAGE)
    handle.write(page(1, 8 * PAGE), 0)
    stats = sess.cluster.stats
    wbytes = stats.write_bytes_snapshot()
    assert sum(wbytes.values()) == 8 * PAGE
    # the per-session ledger carries the same signal
    assert sess.stats.write_bytes_snapshot() == wbytes
    rbytes_before = dict(stats.read_bytes_snapshot())
    handle.read(0, 8 * PAGE)
    # reads do not pollute the write-skew signal and vice versa
    assert stats.write_bytes_snapshot() == wbytes
    assert sum(stats.read_bytes_snapshot().values()) > sum(
        rbytes_before.values()
    )
    sess.cluster.close()


# ------------------------- sync/pipelined equivalence -------------------------


def test_sync_and_pipelined_writes_are_semantically_identical():
    a = make_session(cache_bytes=0, sync_write=False)
    b = make_session(cache_bytes=0, sync_write=True)
    ha, hb = a.create(16 * PAGE, PAGE), b.create(16 * PAGE, PAGE)
    patches = [(0, page(1, 2 * PAGE)), (4 * PAGE, page(2, PAGE)),
               (2 * PAGE, page(3, 4 * PAGE))]
    assert ha.writev(patches) == hb.writev(patches)
    for v in (1, 2, 3):
        np.testing.assert_array_equal(
            ha.read(0, 16 * PAGE, version=v).data,
            hb.read(0, 16 * PAGE, version=v).data,
        )
    a.cluster.close()
    b.cluster.close()


# ------------------------------ compare tool ----------------------------------


def test_benchmark_compare_diffs_rows():
    from benchmarks.compare import diff_rows

    old = {"git_rev": "aaa", "rows": [
        {"mode": "write", "clients": 16, "aggregate_MBps": 10.0},
        {"mode": "gone", "clients": 16, "aggregate_MBps": 5.0},
    ]}
    new = {"git_rev": "bbb", "rows": [
        {"mode": "write", "clients": 16, "aggregate_MBps": 15.0},
        {"mode": "stream-write", "clients": 16, "aggregate_MBps": 30.0},
    ]}
    lines = diff_rows(old, new)
    joined = "\n".join(lines)
    assert "write,16,10.0,15.0,+50.0%" in joined
    # a mode added since the previous payload reports "new", never a crash
    assert "stream-write,16,-,30.0,new" in joined
    assert "gone,16,5.0,-,removed" in joined


def test_benchmark_compare_tolerates_malformed_rows():
    """Rows missing keys (older payload schemas) must degrade to '?' cells,
    not crash the trajectory report."""
    from benchmarks.compare import diff_rows

    old = {"git_rev": "aaa", "rows": [
        {"mode": "write", "clients": 16},  # no aggregate_MBps recorded
    ]}
    new = {"git_rev": "bbb", "rows": [
        {"mode": "write", "clients": 16, "aggregate_MBps": 15.0},
        {"mode": "multi-session", "clients": 16, "aggregate_MBps": 99.0},
    ]}
    joined = "\n".join(diff_rows(old, new))
    assert "write,16,?,15.0,?" in joined
    assert "multi-session,16,-,99.0,new" in joined
