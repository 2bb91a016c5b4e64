"""Tests for the skew-aware parallel data plane (PR 2).

Covers: heap-based bulk placement complexity, DataProvider thread-safety,
batched version assignment (journal byte-compatibility with the single-patch
API), interval-indexed traverse_batch equivalence vs the reference traversal,
replica fallback when a provider dies mid-readv, and adaptive hot-page
promotion/demotion.
"""

import threading

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import (
    BalancerConfig,
    Cluster,
    DataProvider,
    IntervalIndex,
    NodeKey,
    ProviderManager,
    VersionManager,
    traverse,
    traverse_batch,
)

PAGE = 64


def make_session(**kw):
    session_kw = {
        k: kw.pop(k)
        for k in ("cache_bytes", "replica_spread", "sync_write", "max_inflight_writes")
        if k in kw
    }
    session_kw.setdefault("cache_bytes", 0)
    kw.setdefault("n_data_providers", 8)
    kw.setdefault("n_metadata_providers", 4)
    kw.setdefault("shared_cache_bytes", 0)
    return Cluster(**kw).session(**session_kw)


# --------------------------- placement ---------------------------------------


def test_bulk_allocation_is_heap_not_per_page_sort():
    """16k-page placement must cost O(n·r·log P) heap ops, not a per-page
    full sort (O(n·P) comparisons at minimum)."""
    n_providers, n_pages = 64, 16384
    mgr = ProviderManager(replication=1)
    for i in range(n_providers):
        mgr.register(DataProvider(i))
    mgr.placement_ops = 0
    mgr.allocate(n_pages)
    # 2 ops per page (pop + push) plus slack for stale entries; a per-page
    # sort would have been >= n_pages * n_providers comparisons
    assert mgr.placement_ops <= 4 * n_pages
    assert mgr.placement_ops < n_pages * n_providers


def test_bulk_allocation_stays_balanced_with_replication():
    n_providers = 10
    mgr = ProviderManager(replication=3)
    for i in range(n_providers):
        mgr.register(DataProvider(i))
    out = mgr.allocate(500)
    assert len(out) == 500
    for primary, replicas in out:
        pids = [primary[0]] + [pid for pid, _ in replicas]
        assert len(set(pids)) == 3  # all distinct
        keys = {primary[1]} | {k for _, k in replicas}
        assert len(keys) == 1  # replicas share the page key
    loads = mgr.load_snapshot()
    assert sum(loads.values()) == 500 * 3
    assert max(loads.values()) - min(loads.values()) <= 1  # least-loaded


def test_allocation_balances_after_release_and_churn():
    mgr = ProviderManager(replication=1)
    for i in range(4):
        mgr.register(DataProvider(i))
    first = mgr.allocate(40)
    # free provider 0's pages: it must become the placement target again
    mine = [p for p, _ in first if p[0] == 0]
    mgr.release(mine)
    nxt = mgr.allocate(len(mine))
    assert all(p[0] == 0 for p, _ in nxt)
    mgr.deregister(2)
    out = mgr.allocate(30)
    assert all(p[0] != 2 for p, _ in out)


# ------------------------ provider thread-safety ------------------------------


def test_provider_mutation_concurrent_with_iteration():
    """put_pages/delete_pages racing used_bytes/n_pages must never raise
    "dict changed size during iteration"."""
    provider = DataProvider(0)
    stop = threading.Event()
    errors = []

    def mutator():
        i = 0
        try:
            while not stop.is_set():
                provider.put_pages([(i % 97, np.ones(256, np.uint8))])
                provider.delete_pages([(i + 31) % 97])
                i += 1
        except Exception as e:  # pragma: no cover
            errors.append(e)

    def observer():
        try:
            while not stop.is_set():
                provider.used_bytes()
                provider.n_pages
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=mutator) for _ in range(2)] + [
        threading.Thread(target=observer) for _ in range(2)
    ]
    for t in threads:
        t.start()
    timer = threading.Timer(1.0, stop.set)
    timer.start()
    for t in threads:
        t.join()
    timer.cancel()
    assert not errors


# ------------------------ batched version assignment --------------------------


def test_assign_versions_matches_assign_version_loop():
    """Batch assignment must produce the same versions, links and journal as
    the equivalent loop of single assignments."""
    spans = [(0, 4), (2, 3), (6, 2), (0, 8)]
    vm_batch, vm_loop = VersionManager(), VersionManager()
    b1 = vm_batch.alloc(8, PAGE)
    b2 = vm_loop.alloc(8, PAGE)
    got_batch = vm_batch.assign_versions(b1, spans)
    got_loop = [vm_loop.assign_version(b2, o, s) for o, s in spans]
    assert got_batch == got_loop
    assert vm_batch.journal == vm_loop.journal


def test_recover_replays_batch_assigned_journal():
    """Journal produced through writev's batch assignment must replay through
    VersionManager.recover exactly like the single-patch journal (regression
    for the thin-wrapper guarantee)."""
    sess = make_session()
    handle = sess.create(16 * PAGE, PAGE)
    blob = handle.blob_id
    handle.writev(
        [
            (0, np.full(2 * PAGE, 1, np.uint8)),
            (4 * PAGE, np.full(2 * PAGE, 2, np.uint8)),
            (2 * PAGE, np.full(4 * PAGE, 3, np.uint8)),
        ],
    )
    vm = sess.cluster.version_manager
    journal = vm.journal
    assert [e.op for e in journal] == ["alloc"] + ["assign"] * 3 + ["complete"] * 3
    vm2, orphans = VersionManager.recover(journal)
    assert vm2.latest_published(blob) == 3
    assert orphans[blob] == []
    for v in (1, 2, 3):
        assert vm2.interval_of(blob, v) == vm.interval_of(blob, v)
    sess.cluster.close()


def test_writev_takes_manager_lock_once_for_all_patches(monkeypatch):
    sess = make_session()
    handle = sess.create(16 * PAGE, PAGE)
    calls = []
    vm = sess.cluster.version_manager
    orig = vm.assign_versions

    def counting(blob_id, spans):
        calls.append(list(spans))
        return orig(blob_id, spans)

    monkeypatch.setattr(vm, "assign_versions", counting)
    handle.writev(
        [(0, np.ones(PAGE, np.uint8)), (8 * PAGE, np.ones(2 * PAGE, np.uint8))],
    )
    assert calls == [[(0, 1), (8, 2)]]  # ONE batched call for both patches
    sess.cluster.close()


# ------------------------- interval index + traversal -------------------------


def test_interval_index_queries():
    idx = IntervalIndex([(10, 5), (3, 2), (14, 4), (30, 1)])
    # merged: [3,5) [10,18) [30,31)
    assert idx.starts == [3, 10, 30]
    assert idx.ends == [5, 18, 31]
    assert idx.intersects_any(0, 3) is False
    assert idx.intersects_any(4, 1) is True
    assert idx.intersects_any(5, 5) is False
    assert idx.intersects_any(17, 10) is True
    assert idx.intersects_any(31, 100) is False
    assert list(idx.clip(0, 100)) == [(3, 5), (10, 18), (30, 31)]
    assert list(idx.clip(4, 8)) == [(4, 5), (10, 12)]
    assert list(idx.clip(5, 5)) == []


@st.composite
def range_sets(draw):
    total_pages = draw(st.sampled_from([8, 16, 32, 64]))
    n_writes = draw(st.integers(min_value=0, max_value=6))
    writes = []
    for _ in range(n_writes):
        off = draw(st.integers(min_value=0, max_value=total_pages - 1))
        size = draw(st.integers(min_value=1, max_value=total_pages - off))
        writes.append((off, size))
    n_ranges = draw(st.integers(min_value=1, max_value=8))
    ranges = []
    for _ in range(n_ranges):
        off = draw(st.integers(min_value=0, max_value=total_pages - 1))
        size = draw(st.integers(min_value=0, max_value=total_pages - off))
        ranges.append((off, size))
    return total_pages, writes, ranges


@settings(max_examples=40, deadline=None)
@given(range_sets())
def test_traverse_batch_equivalent_to_traverse(case):
    """Property: for ANY write history and ANY randomized range set, the
    interval-indexed batch traversal returns exactly the union of what the
    reference single-range traversal yields per range."""
    total_pages, writes, ranges = case
    sess = make_session(n_data_providers=4)
    handle = sess.create(total_pages * PAGE, PAGE)
    blob = handle.blob_id
    for i, (off, size) in enumerate(writes):
        handle.write(np.full(size * PAGE, (i % 250) + 1, np.uint8), off * PAGE)
    version = handle.latest_published()
    metadata = sess.cluster.metadata

    batch = traverse_batch(
        metadata.get_nodes, blob, version, total_pages, ranges
    )
    expected = {}
    for off, size in ranges:
        if size == 0:
            continue
        for page, leaf in traverse(
            metadata.get_node, blob, version, total_pages, off, size
        ):
            expected[page] = leaf
    assert set(batch) == set(expected)
    for page in expected:
        if expected[page] is None:
            assert batch[page] is None
        else:
            assert batch[page] is not None
            assert batch[page].key == expected[page].key
    sess.cluster.close()


# ----------------------- replica fallback / promotion -------------------------


def test_readv_replica_fallback_when_provider_dies_mid_read():
    """A provider failing between the metadata traversal and the page fetch
    must be survived through replicas (the batch fails, per-page fallback
    succeeds). ``replica_spread=False`` pins fetches to the primary, so
    killing a leaf's primary deterministically exercises the fallback."""
    sess = make_session(
        n_data_providers=4, page_replication=2, replica_spread=False
    )
    cluster = sess.cluster
    handle = sess.create(8 * PAGE, PAGE)
    payload = np.arange(8 * PAGE, dtype=np.uint8)
    handle.write(payload, 0)

    real_traverse = traverse_batch
    killed = []

    def killing_get_nodes(keys):
        got = cluster.metadata.get_nodes(keys)
        if not killed and any(k.size == 1 for k in got):
            # some leaves resolved: kill a primary before pages are fetched
            leaf = next(n for n in got.values() if n.is_leaf)
            cluster.provider_manager.fail_provider(leaf.page[0])
            killed.append(leaf.page[0])
        return got

    import repro.core.cluster as cluster_mod

    orig = cluster_mod.traverse_batch
    # the stub get_nodes ignores the streaming on_partial hook, so leaves
    # reach the fetch stream only via the level-end on_leaves emission —
    # which happens AFTER killing_get_nodes returned and killed the primary
    cluster_mod.traverse_batch = (
        lambda get_nodes, *a, **kw: real_traverse(killing_get_nodes, *a, **kw)
    )
    try:
        outs = handle.readv([(0, 8 * PAGE)])
    finally:
        cluster_mod.traverse_batch = orig
    assert killed, "test harness never killed a provider"
    np.testing.assert_array_equal(outs[0], payload)
    cluster.close()


def hammer(handle, offset, size, n=200):
    for _ in range(n):
        handle.read(offset, size)


def test_hot_page_promotion_appears_in_all_page_refs_and_spreads_reads():
    sess = make_session(
        n_data_providers=8,
        balancer_config=BalancerConfig(
            hot_threshold=4, skew_ratio=1.2, check_interval=16
        ),
    )
    cluster = sess.cluster
    handle = sess.create(16 * PAGE, PAGE)
    blob = handle.blob_id
    handle.write(np.ones(16 * PAGE, np.uint8), 0)
    cluster.stats.reset()
    hammer(handle, 0, PAGE)
    bal = cluster.replica_balancer
    assert bal.promotions > 0
    leaf = cluster.metadata.get_node(NodeKey(blob, 1, 0, 1))
    assert len(leaf.all_page_refs()) == 1 + bal.promotions
    assert bal.promoted_refs(leaf.key) == leaf.replicas
    # reads actually spread: multiple providers served read bytes
    served = {pid for pid, b in cluster.stats.read_bytes_snapshot().items() if b > 0}
    assert len(served) > 1
    # the promoted copies hold the same immutable bytes
    for pid, key in leaf.all_page_refs():
        np.testing.assert_array_equal(
            cluster.provider_manager.get_provider(pid).get_page(key),
            np.ones(PAGE, np.uint8),
        )
    cluster.close()


def test_hot_page_demotion_restores_primary_only_and_frees_copies():
    sess = make_session(
        n_data_providers=8,
        balancer_config=BalancerConfig(
            hot_threshold=4, skew_ratio=1.2, check_interval=16
        ),
    )
    cluster = sess.cluster
    handle = sess.create(16 * PAGE, PAGE)
    handle.write(np.ones(16 * PAGE, np.uint8), 0)
    hammer(handle, 0, PAGE)
    bal = cluster.replica_balancer
    key = NodeKey(handle.blob_id, 1, 0, 1)
    promoted = bal.promoted_refs(key)
    assert promoted
    dropped = bal.demote(key)
    assert dropped == len(promoted)
    leaf = cluster.metadata.get_node(key)
    assert leaf.replicas == ()
    for pid, page_key in promoted:
        assert not cluster.provider_manager.get_provider(pid).has_page(page_key)
    # the page is still readable from its primary
    np.testing.assert_array_equal(
        handle.read(0, PAGE).data, np.ones(PAGE, np.uint8)
    )
    cluster.close()


def test_promotion_survives_primary_failure_without_write_replication():
    """Adaptive replication gives fault tolerance the write path never paid
    for: page_replication=1, but a promoted hot page survives primary loss."""
    sess = make_session(
        n_data_providers=8,
        balancer_config=BalancerConfig(
            hot_threshold=4, skew_ratio=1.2, check_interval=16
        ),
    )
    cluster = sess.cluster
    handle = sess.create(16 * PAGE, PAGE)
    handle.write(np.full(16 * PAGE, 7, np.uint8), 0)
    hammer(handle, 0, PAGE)
    leaf = cluster.metadata.get_node(NodeKey(handle.blob_id, 1, 0, 1))
    assert len(leaf.all_page_refs()) > 1
    cluster.provider_manager.fail_provider(leaf.page[0])
    np.testing.assert_array_equal(
        handle.read(0, PAGE).data, np.full(PAGE, 7, np.uint8)
    )
    cluster.close()


def test_gc_demotes_and_forgets_promoted_pages():
    sess = make_session(
        n_data_providers=8,
        balancer_config=BalancerConfig(
            hot_threshold=4, skew_ratio=1.2, check_interval=16
        ),
    )
    cluster = sess.cluster
    handle = sess.create(16 * PAGE, PAGE)
    handle.write(np.ones(16 * PAGE, np.uint8), 0)  # v1
    hammer(handle, 0, PAGE)
    bal = cluster.replica_balancer
    key = NodeKey(handle.blob_id, 1, 0, 1)
    n_promoted = len(bal.promoted_refs(key))
    assert n_promoted > 0
    promoted = bal.promoted_refs(key)
    handle.write(np.full(16 * PAGE, 2, np.uint8), 0)  # v2 rewrites all
    _, pages_freed, _ = cluster.gc(handle.blob_id, keep_versions=[2])
    # v1's 16 pages die, including the promoted copies of the hot page
    assert pages_freed == 16 + n_promoted
    assert bal.promoted_refs(key) == ()
    for pid, page_key in promoted:
        assert not cluster.provider_manager.get_provider(pid).has_page(page_key)
    cluster.close()


def test_repromotion_after_demote_never_resurrects_dropped_refs():
    """Regression: a reader holding a pre-demotion node must not leak the
    dropped replica refs back into the metadata DHT via the balancer's heat
    records — every ref published after re-promotion must point to a live
    page copy."""
    sess = make_session(
        n_data_providers=8,
        balancer_config=BalancerConfig(
            hot_threshold=4, skew_ratio=1.2, check_interval=16
        ),
    )
    cluster = sess.cluster
    handle = sess.create(16 * PAGE, PAGE)
    handle.write(np.ones(16 * PAGE, np.uint8), 0)
    key = NodeKey(handle.blob_id, 1, 0, 1)
    bal = cluster.replica_balancer
    hammer(handle, 0, PAGE)
    assert bal.promoted_refs(key)
    bal.demote(key)
    hammer(handle, 0, PAGE)  # heat builds again: re-promotion allowed
    leaf = cluster.metadata.get_node(key)
    for pid, page_key in leaf.all_page_refs():
        assert cluster.provider_manager.get_provider(pid).has_page(page_key), (
            f"leaf publishes dead ref ({pid}, {page_key})"
        )
    cluster.close()


def test_promotion_skips_failed_target_providers():
    """Regression: a failed cold provider must not be picked as the promotion
    target (that would silently block promotion cluster-wide)."""
    sess = make_session(
        n_data_providers=8,
        balancer_config=BalancerConfig(
            hot_threshold=4, skew_ratio=1.2, check_interval=16
        ),
    )
    cluster = sess.cluster
    handle = sess.create(16 * PAGE, PAGE)
    handle.write(np.ones(16 * PAGE, np.uint8), 0)
    leaf = cluster.metadata.get_node(NodeKey(handle.blob_id, 1, 0, 1))
    # fail every provider except the hot page's primary and one target
    alive_target = next(
        p.provider_id
        for p in cluster.provider_manager.providers()
        if p.provider_id != leaf.page[0]
    )
    for p in cluster.provider_manager.providers():
        if p.provider_id not in (leaf.page[0], alive_target):
            cluster.provider_manager.fail_provider(p.provider_id)
    hammer(handle, 0, PAGE)
    bal = cluster.replica_balancer
    assert bal.promotions >= 1
    assert all(pid == alive_target for pid, _ in bal.promoted_refs(leaf.key))
    cluster.close()


def test_replica_spread_off_always_uses_primary():
    sess = make_session(
        n_data_providers=8, page_replication=2, replica_spread=False,
        hot_replicas=False,
    )
    cluster = sess.cluster
    handle = sess.create(8 * PAGE, PAGE)
    handle.write(np.ones(8 * PAGE, np.uint8), 0)
    cluster.stats.reset()
    for _ in range(20):
        handle.read(0, 8 * PAGE)
    served = set(cluster.stats.read_bytes_snapshot())
    primaries = set()
    for p in range(8):
        primaries.add(
            cluster.metadata.get_node(NodeKey(handle.blob_id, 1, p, 1)).page[0]
        )
    assert served == primaries  # replicas never served
    cluster.close()
