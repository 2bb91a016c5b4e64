"""Self-healing data plane: health states, retry/backoff, mid-flight write
re-placement, background re-replication, writer recovery, and the seeded
chaos harness.

The chaos tests drive live mixed traffic from several sessions while a
deterministic :class:`FaultSchedule` kills/recovers providers and injects
RPC drops/delays, then assert the interleaving-independent invariants the
paper's lock-free design must hold: zero data loss for published versions,
a monotone publish frontier, and replication-factor restoration after
repair.
"""

import threading
import traceback

import numpy as np
import pytest

from repro.core import (
    Cluster,
    DataProvider,
    FaultEvent,
    FaultInjector,
    FaultSchedule,
    HealthConfig,
    MetadataDHT,
    ProviderFailed,
    ProviderManager,
    RetryPolicy,
    TrafficStats,
    VersionManager,
    page_checksum,
)
from repro.core import Federation
from repro.core.faults import DELAY, DROP, KILL, METADATA, NODE, RECOVER

PAGE = 256


class FakeClock:
    def __init__(self, start=1000.0):
        self.now = start

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


def make_manager(n=4, replication=1, **health_kw):
    clock = health_kw.pop("clock", FakeClock())
    mgr = ProviderManager(
        replication=replication,
        stats=TrafficStats(),
        health=HealthConfig(clock=clock, **health_kw),
    )
    for i in range(n):
        mgr.register(DataProvider(i))
    return mgr, clock


# ----------------------------- health machine ---------------------------------


def test_health_state_machine_live_suspect_dead():
    mgr, clock = make_manager(suspect_after=1, dead_after=3, window_seconds=10.0)
    assert mgr.health_state(0) == "live"
    mgr.note_failure(0)
    assert mgr.health_state(0) == "suspect"
    mgr.note_failure(0)
    assert mgr.health_state(0) == "suspect"
    mgr.note_failure(0)
    assert mgr.health_state(0) == "dead"
    assert mgr.dead_providers() == [0]
    # an observed success is the recovery signal
    mgr.note_success(0)
    assert mgr.health_state(0) == "live"
    assert mgr.dead_providers() == []


def test_health_failures_decay_outside_window():
    mgr, clock = make_manager(suspect_after=1, dead_after=3, window_seconds=10.0)
    mgr.note_failure(0)
    mgr.note_failure(0)
    clock.advance(11.0)  # both failures age out of the window
    assert mgr.health_state(0) == "live"
    mgr.note_failure(0)  # fresh failure alone: suspect, NOT dead
    assert mgr.health_state(0) == "suspect"
    assert mgr.dead_providers() == []


def test_on_dead_fires_exactly_once_outside_lock():
    mgr, _ = make_manager(dead_after=2)
    deaths = []
    mgr.on_dead = deaths.append
    for _ in range(5):
        mgr.note_failure(1)
    assert deaths == [1]  # once per death, not per failure
    mgr.note_success(1)
    mgr.note_failure(1)
    mgr.note_failure(1)
    assert deaths == [1, 1]  # a NEW death after recovery fires again


def test_healthy_providers_excludes_suspect_and_failed():
    mgr, _ = make_manager(suspect_after=1, dead_after=3)
    mgr.note_failure(0)
    mgr.fail_provider(1)
    healthy = {p.provider_id for p in mgr.healthy_providers()}
    assert healthy == {2, 3}


# --------------------------- placement satellites ------------------------------


def test_allocate_skips_failed_and_dead_providers():
    """Satellite regression: fresh pages must never land on a provider whose
    failure flag is set or that the health machine declared dead."""
    mgr, _ = make_manager(n=4, replication=2, dead_after=1)
    mgr.fail_provider(0)
    mgr.note_failure(3)  # dead_after=1 -> declared dead
    out = mgr.allocate(40)
    pids = {pid for primary, replicas in out for pid, _ in (primary,) + replicas}
    assert pids == {1, 2}
    # suspect providers STAY placeable (one blip must not evict a node)
    mgr2, _ = make_manager(n=2, replication=1, suspect_after=1, dead_after=3)
    mgr2.note_failure(0)
    assert mgr2.health_state(0) == "suspect"
    assert {p for (p, _), _ in mgr2.allocate(10)} == {0, 1}


def test_allocate_raises_only_when_healthy_below_replication():
    mgr, _ = make_manager(n=3, replication=2)
    mgr.fail_provider(0)
    mgr.allocate(4)  # 2 healthy of 3: still satisfiable
    mgr.fail_provider(1)
    with pytest.raises(ProviderFailed, match="1 healthy providers"):
        mgr.allocate(4)
    mgr.recover_provider(1)
    assert mgr.allocate(4)  # recovery restores placement immediately


def test_recovered_provider_resurfaces_in_placement():
    mgr, _ = make_manager(n=2, replication=1)
    mgr.fail_provider(0)
    assert {p for (p, _), _ in mgr.allocate(6)} == {1}
    mgr.recover_provider(0)
    pids = {p for (p, _), _ in mgr.allocate(8)}
    assert 0 in pids  # least-loaded now, must be discoverable again


def test_deregister_releases_load_credit():
    """Satellite: a departing provider's outstanding load credit must not
    haunt the books (it skewed every later least-loaded decision)."""
    mgr, _ = make_manager(n=2, replication=1)
    placements = mgr.allocate(10)
    held = sum(1 for (pid, _), _ in placements if pid == 0)
    assert mgr.deregister(0) == held
    assert 0 not in mgr.load_snapshot()
    # the remaining provider's credit is untouched
    assert mgr.load_snapshot()[1] == 10 - held


def test_unknown_provider_ids_raise_clear_keyerror():
    mgr, _ = make_manager(n=2)
    for op in (mgr.get_provider, mgr.fail_provider, mgr.recover_provider,
               mgr.health_state):
        with pytest.raises(KeyError, match="unknown data provider id 99"):
            op(99)


# ------------------------------ retry policy -----------------------------------


def test_retry_policy_deterministic_and_bounded():
    a = RetryPolicy(seed=7)
    delays = [a.delay(k) for k in range(5)]
    # replayable: an instance's delay stream is a pure function of
    # (seed, nonce, attempt) — pin the nonce to replay another instance's
    # exact stream (e.g. when reproducing a logged chaos run)
    replay = RetryPolicy(seed=7, nonce=a.nonce)
    assert delays == [replay.delay(k) for k in range(5)]
    assert delays[0] < delays[1] < delays[2]  # exponential growth
    for k, d in enumerate(delays):
        assert d <= a.max_delay_seconds * (1 + a.jitter)
    assert RetryPolicy(seed=8, nonce=a.nonce).delay(1) != a.delay(1)


def test_retry_policy_instances_desynchronize():
    """Satellite bugfix: two same-seed policies used to produce IDENTICAL
    jitter streams, so every concurrent client backing off from the same
    hot provider retried in lockstep — synchronized retry storms, exactly
    what jitter exists to prevent. Each instance now mixes a per-instance
    nonce into the stream, so concurrent policies diverge."""
    a = RetryPolicy(seed=7)
    b = RetryPolicy(seed=7)
    assert a.nonce != b.nonce
    assert [a.delay(k) for k in range(5)] != [b.delay(k) for k in range(5)]


def test_put_batch_retries_transient_failure_then_succeeds():
    """A provider that blips for one RPC must not fail the write: the retry
    layer absorbs it (and counts it), the health machine sees both sides."""
    slept = []
    cluster = Cluster(
        n_data_providers=2, shared_cache_bytes=0,
        retry_policy=RetryPolicy(max_attempts=3, sleep=slept.append),
    )
    provider = cluster.provider_manager.get_provider(0)
    real_put = provider.put_pages
    blips = {"left": 1}

    def flaky_put(items):
        if blips["left"]:
            blips["left"] -= 1
            raise ProviderFailed("injected blip")
        return real_put(items)

    provider.put_pages = flaky_put
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(8 * PAGE, PAGE)
    v = handle.write(np.full(4 * PAGE, 9, np.uint8), 0)
    assert slept, "backoff must have run"
    assert cluster.stats.retries >= 1
    np.testing.assert_array_equal(
        handle.read(0, 4 * PAGE, version=v).data, np.full(4 * PAGE, 9, np.uint8)
    )
    assert cluster.provider_manager.health_state(0) == "live"  # success cleared
    cluster.close()


def test_writev_replaces_dead_providers_batch_midflight():
    """Tentpole: a provider that dies AFTER placement does not abort the
    writev — its batch is re-put on healthy providers, the leaves are
    corrected, and the version publishes with full replication."""
    cluster = Cluster(
        n_data_providers=3, page_replication=2, shared_cache_bytes=0,
        retry_policy=RetryPolicy(max_attempts=2, sleep=lambda s: None),
        health=HealthConfig(dead_after=1),
    )
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(8 * PAGE, PAGE)
    provider = cluster.provider_manager.get_provider(0)
    started, release = threading.Event(), threading.Event()
    real_put = provider.put_pages

    def dying_put(items):
        started.set()
        assert release.wait(10)
        return real_put(items)

    provider.put_pages = dying_put
    versions = []
    t = threading.Thread(
        target=lambda: versions.append(handle.write(np.full(6 * PAGE, 5, np.uint8), 0))
    )
    t.start()
    assert started.wait(10)
    cluster.provider_manager.fail_provider(0)  # dies mid-flight
    release.set()
    t.join(10)
    assert versions == [1], "write must complete despite the death"
    # the published version's leaves reference only live providers
    for key, node in cluster.metadata.iter_nodes(handle.blob_id):
        if node.is_leaf:
            pids = [pid for pid, _ in node.all_page_refs()]
            assert 0 not in pids
            assert len(set(pids)) == 2  # replication preserved
    # and the data is truly there (no cache: straight from the providers)
    np.testing.assert_array_equal(
        handle.read(0, 6 * PAGE, version=1).data, np.full(6 * PAGE, 5, np.uint8)
    )
    assert cluster.stats.retries >= 1
    cluster.close()


def test_degraded_read_falls_back_and_counts():
    """Reads of data with a dead replica complete through the survivors and
    are counted as degraded (the operator's signal that redundancy is low)."""
    cluster = Cluster(n_data_providers=3, page_replication=2,
                      shared_cache_bytes=0)
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(8 * PAGE, PAGE)
    data = np.arange(8 * PAGE, dtype=np.uint8)
    v = handle.write(data, 0)
    cluster.provider_manager.fail_provider(0)
    out = handle.read(0, 8 * PAGE, version=v).data
    np.testing.assert_array_equal(out, data)
    assert cluster.stats.replica_fallbacks >= 1
    assert cluster.stats.degraded_reads >= 1
    assert cluster.provider_manager.health_state(0) in ("suspect", "dead")
    cluster.close()


# ------------------------------- repair ----------------------------------------


def test_repair_restores_replication_factor():
    """Re-replication: after a provider is declared dead, a repair pass
    copies its published pages from survivors onto healthy providers and
    rewrites the leaves — the replication factor is whole again."""
    cluster = Cluster(n_data_providers=4, page_replication=2,
                      shared_cache_bytes=0, health=HealthConfig(dead_after=1))
    pm = cluster.provider_manager
    pm.on_dead = None  # drive the pass by hand for determinism
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(16 * PAGE, PAGE)
    data = np.random.default_rng(3).integers(0, 255, 16 * PAGE, dtype=np.uint8)
    v = handle.write(data, 0)
    pm.fail_provider(0)
    pm.note_failure(0)
    assert pm.dead_providers() == [0]
    repaired, _ = cluster.repair_service.run_once()
    assert repaired > 0
    assert cluster.stats.repaired_pages == repaired
    for key, node in cluster.metadata.iter_nodes(handle.blob_id):
        if node.is_leaf:
            refs = node.all_page_refs()
            pids = {pid for pid, _ in refs}
            assert 0 not in pids, "leaves must stop referencing the dead node"
            assert len(pids) == 2, "replication factor restored"
            for pid, page_key in refs:
                assert pm.get_provider(pid).has_page(page_key)
    np.testing.assert_array_equal(
        sess.open(handle.blob_id).read(0, 16 * PAGE, version=v).data, data
    )
    cluster.close()


def test_repair_tops_up_leaves_an_earlier_pass_left_short():
    """A pass with no healthy target still drops the dead provider's refs;
    a later pass, with nothing down any more, must restore the leaves to
    the replication factor."""
    cluster = Cluster(n_data_providers=2, page_replication=2,
                      shared_cache_bytes=0, health=HealthConfig(dead_after=1))
    pm = cluster.provider_manager
    pm.on_dead = None  # drive the passes by hand for determinism
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(4 * PAGE, PAGE)
    data = np.random.default_rng(5).integers(0, 255, 4 * PAGE, dtype=np.uint8)
    v = handle.write(data, 0)

    def leaf_refs():
        return [
            node.all_page_refs()
            for _, node in cluster.metadata.iter_nodes(handle.blob_id)
            if node.is_leaf
        ]

    pm.fail_provider(0)
    pm.note_failure(0)
    cluster.repair_service.run_once()  # no target for the lost copies
    assert all({pid for pid, _ in refs} == {1} for refs in leaf_refs())
    pm.recover_provider(0)
    repaired, _ = cluster.repair_service.run_once()
    assert repaired == 4
    for refs in leaf_refs():
        assert {pid for pid, _ in refs} == {0, 1}
        for pid, page_key in refs:
            assert pm.get_provider(pid).has_page(page_key)
    np.testing.assert_array_equal(
        sess.open(handle.blob_id).read(0, 4 * PAGE, version=v).data, data
    )
    cluster.close()


def test_death_schedules_background_repair():
    """The on_dead hook queues repair on the aux pool automatically."""
    cluster = Cluster(n_data_providers=4, page_replication=2,
                      shared_cache_bytes=0, health=HealthConfig(dead_after=1))
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(8 * PAGE, PAGE)
    handle.write(np.full(8 * PAGE, 3, np.uint8), 0)
    cluster.provider_manager.fail_provider(1)
    cluster.provider_manager.note_failure(1)  # -> dead -> schedule()
    deadline = threading.Event()
    for _ in range(200):  # aux-pool pass is asynchronous: poll briefly
        if all(
            1 not in {pid for pid, _ in node.all_page_refs()}
            for key, node in cluster.metadata.iter_nodes(handle.blob_id)
            if node.is_leaf
        ):
            break
        deadline.wait(0.02)
    assert cluster.repair_service.last_error is None
    assert all(
        1 not in {pid for pid, _ in node.all_page_refs()}
        for key, node in cluster.metadata.iter_nodes(handle.blob_id)
        if node.is_leaf
    )
    cluster.close()


def test_unrepairable_when_all_replicas_dead_is_skipped():
    cluster = Cluster(n_data_providers=2, page_replication=2,
                      shared_cache_bytes=0, health=HealthConfig(dead_after=1))
    pm = cluster.provider_manager
    pm.on_dead = None
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(4 * PAGE, PAGE)
    handle.write(np.full(4 * PAGE, 1, np.uint8), 0)
    for pid in (0, 1):
        pm.fail_provider(pid)
        pm.note_failure(pid)
    repaired, _ = cluster.repair_service.run_once()
    assert repaired == 0  # nothing to copy FROM; no crash, no bogus rewrite
    cluster.close()


# --------------------------- writer recovery / scrub ---------------------------


def _make_hole(cluster, sess, handle):
    """Drive a writer into a publication hole: writer A blocks on its data
    put, writer B is assigned after it, then every provider dies so A's
    re-placement finds no target and A aborts. Returns B's version."""
    blob = handle.blob_id
    provider = cluster.provider_manager.get_provider(0)
    started, release = threading.Event(), threading.Event()
    real_put = provider.put_pages

    def blocked_put(items):
        started.set()
        assert release.wait(10)
        return real_put(items)

    provider.put_pages = blocked_put
    failures = []

    def writer_a():
        try:
            handle.write(np.full(PAGE, 1, np.uint8), 0)
        except ProviderFailed as err:
            failures.append(err)

    t = threading.Thread(target=writer_a)
    t.start()
    assert started.wait(10)
    for _ in range(500):
        if cluster.version_manager.assigned_versions(blob) >= 1:
            break
        threading.Event().wait(0.01)
    v2 = cluster.session(cache_bytes=0).open(blob).write(
        np.full(PAGE, 2, np.uint8), PAGE
    )
    for pid in (0, 1):
        cluster.provider_manager.fail_provider(pid)
    release.set()
    t.join(10)
    provider.put_pages = real_put
    assert failures, "A must abort once no healthy target remains"
    cluster.provider_manager.recover_provider(1)
    return v2


def test_hole_readers_redirect_around_dangling_links():
    """Writer recovery, read side: B published with border links woven
    against A's hole; readers resolve them to surviving versions instead of
    crashing on missing nodes."""
    cluster = Cluster(n_data_providers=2, shared_cache_bytes=0,
                      retry_policy=RetryPolicy(max_attempts=1))
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(8 * PAGE, PAGE)
    v2 = _make_hole(cluster, sess, handle)
    vm = cluster.version_manager
    assert vm.latest_published(handle.blob_id) == v2
    assert vm.aborted_view(handle.blob_id) == frozenset([1])
    reader = cluster.session(cache_bytes=0).open(handle.blob_id)
    np.testing.assert_array_equal(
        reader.read(PAGE, PAGE, version=v2).data, np.full(PAGE, 2, np.uint8)
    )
    # the region A never published reads as zeros, not as A's lost bytes
    np.testing.assert_array_equal(
        reader.read(0, PAGE, version=v2).data, np.zeros(PAGE, np.uint8)
    )
    cluster.close()


def test_scrub_unlinks_dangling_links_and_reclaims_wreckage():
    """Writer recovery, scrub side: the metadata scrub rewrites inner links
    pointing into the hole and deletes the hole's stored nodes/pages —
    reads stay correct before AND after."""
    cluster = Cluster(n_data_providers=2, shared_cache_bytes=0,
                      retry_policy=RetryPolicy(max_attempts=1))
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(8 * PAGE, PAGE)
    blob = handle.blob_id
    v2 = _make_hole(cluster, sess, handle)
    hole_nodes_before = [
        key for key, _ in cluster.metadata.iter_nodes(blob) if key.version == 1
    ]
    scrubbed = cluster.repair_service.scrub(blob)
    assert scrubbed >= len(hole_nodes_before)
    assert not any(
        key.version == 1 for key, _ in cluster.metadata.iter_nodes(blob)
    ), "hole wreckage gone"
    assert not any(
        node.left_version == 1 or node.right_version == 1
        for _, node in cluster.metadata.iter_nodes(blob)
        if not node.is_leaf
    ), "no stored link reaches the hole anymore"
    reader = cluster.session(cache_bytes=0).open(blob)
    np.testing.assert_array_equal(
        reader.read(PAGE, PAGE, version=v2).data, np.full(PAGE, 2, np.uint8)
    )
    cluster.close()


def test_abandon_journal_replay_reconstructs_state():
    """Satellite: recover() on a journal with interleaved assign / success /
    abandon entries rebuilds the same publish frontier, holes, and per-page
    version array as the live manager."""
    vm = VersionManager()
    blob = vm.alloc(16, PAGE)
    v1, _ = vm.assign_version(blob, 0, 4)
    v2, _ = vm.assign_version(blob, 2, 4)
    v3, _ = vm.assign_version(blob, 8, 4)
    vm.report_success(blob, v1)
    vm.abandon(blob, [v2])          # hole (v3 assigned after it)
    vm.report_success(blob, v3)
    v4, _ = vm.assign_version(blob, 0, 2)
    vm.abandon(blob, [v4])          # tail hole: the number is never reused
    v5, _ = vm.assign_version(blob, 12, 4)
    assert v5 == v4 + 1
    vm.report_success(blob, v5)

    recovered, orphans = VersionManager.recover(list(vm.journal))
    assert recovered.latest_published(blob) == vm.latest_published(blob)
    assert recovered.aborted_view(blob) == vm.aborted_view(blob)
    assert recovered.assigned_versions(blob) == vm.assigned_versions(blob)
    np.testing.assert_array_equal(
        recovered._blobs[blob].page_versions, vm._blobs[blob].page_versions
    )
    assert orphans == {blob: []}


# ------------------------------ chaos harness ----------------------------------


def test_fault_schedule_generation_is_deterministic_and_bounded():
    a = FaultSchedule.generate(seed=11, n_providers=8, max_dead=2)
    b = FaultSchedule.generate(seed=11, n_providers=8, max_dead=2)
    assert a.events == b.events
    assert a.events != FaultSchedule.generate(seed=12, n_providers=8).events
    dead = set()
    for ev in a.events:
        if ev.action == KILL:
            dead.add(ev.provider_id)
            assert len(dead) <= 2
        elif ev.action == RECOVER:
            dead.discard(ev.provider_id)
    assert not dead, "generate(recover_all=True) must recover everyone"


def test_injector_drop_fails_exactly_one_rpc():
    cluster = Cluster(n_data_providers=1, shared_cache_bytes=0,
                      retry_policy=RetryPolicy(max_attempts=1))
    schedule = FaultSchedule([FaultEvent(1, DROP, 0)])
    injector = FaultInjector(cluster, schedule)
    injector.attach()
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(4 * PAGE, PAGE)
    with pytest.raises(ProviderFailed, match="injected drop"):
        handle.write(np.full(PAGE, 1, np.uint8), 0)
    # the drop was one-shot: the very next write sails through
    v = handle.write(np.full(PAGE, 2, np.uint8), 0)
    injector.detach()
    np.testing.assert_array_equal(
        handle.read(0, PAGE, version=v).data, np.full(PAGE, 2, np.uint8)
    )
    cluster.close()


def test_injector_drop_is_absorbed_by_retry():
    cluster = Cluster(n_data_providers=1, shared_cache_bytes=0,
                      retry_policy=RetryPolicy(max_attempts=3,
                                               sleep=lambda s: None))
    injector = FaultInjector(cluster, FaultSchedule([FaultEvent(1, DROP, 0)]))
    injector.attach()
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(4 * PAGE, PAGE)
    v = handle.write(np.full(PAGE, 7, np.uint8), 0)  # retry absorbs the drop
    injector.detach()
    assert v == 1
    assert cluster.stats.retries >= 1
    cluster.close()


# ----------------------- metadata plane: health + quorum -----------------------


def test_metadata_shard_health_machine():
    clock = FakeClock()
    dht = MetadataDHT(
        4, replication=2,
        health=HealthConfig(suspect_after=1, dead_after=3,
                            window_seconds=10.0, clock=clock),
    )
    assert dht.shard_health(0) == "live"
    dht.note_shard_failure(0)
    assert dht.shard_health(0) == "suspect"
    dht.note_shard_failure(0)
    dht.note_shard_failure(0)
    assert dht.shard_health(0) == "dead"
    assert dht.dead_shards() == [0]
    dht.note_shard_success(0)  # observed success is the recovery signal
    assert dht.shard_health(0) == "live"
    # failures age out of the window instead of accumulating forever
    dht.note_shard_failure(1)
    dht.note_shard_failure(1)
    clock.advance(11.0)
    assert dht.shard_health(1) == "live"


def test_metadata_shard_on_dead_fires_once_and_schedules_repair():
    dht = MetadataDHT(4, replication=2, health=HealthConfig(dead_after=2))
    deaths = []
    dht.on_dead = deaths.append
    for _ in range(5):
        dht.note_shard_failure(2)
    assert deaths == [2]


def test_metadata_write_commits_on_quorum_with_dead_replica():
    """With metadata_replication=2 the write quorum is 1: killing one shard
    loses at most one of each node's two consecutive homes, so writes keep
    committing and reads fall back to the survivor."""
    cluster = Cluster(
        n_data_providers=2, n_metadata_providers=4, metadata_replication=2,
        shared_cache_bytes=0,
        retry_policy=RetryPolicy(max_attempts=2, sleep=lambda s: None),
    )
    cluster.metadata.fail_shard(1)
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(8 * PAGE, PAGE)
    data = np.arange(8 * PAGE, dtype=np.uint8)
    v = handle.write(data, 0)  # must commit: every node keeps >= 1 replica
    np.testing.assert_array_equal(handle.read(0, 8 * PAGE, version=v).data, data)
    cluster.close()


def test_metadata_write_aborts_cleanly_on_quorum_loss():
    """When a node cannot reach its write quorum on ANY replica the writev
    aborts through the normal abandon path — no partial publish, no hang,
    and the frontier stays where it was."""
    cluster = Cluster(
        n_data_providers=2, n_metadata_providers=4, metadata_replication=2,
        shared_cache_bytes=0,
        retry_policy=RetryPolicy(max_attempts=1, sleep=lambda s: None),
    )
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(8 * PAGE, PAGE)
    v = handle.write(np.full(8 * PAGE, 3, np.uint8), 0)
    for sid in range(4):
        cluster.metadata.fail_shard(sid)
    with pytest.raises(ProviderFailed):
        handle.write(np.full(8 * PAGE, 4, np.uint8), 0)
    for sid in range(4):
        cluster.metadata.recover_shard(sid)
    assert handle.latest_published() == v  # frontier unmoved, hole withdrawn
    np.testing.assert_array_equal(
        handle.read(0, 8 * PAGE).data, np.full(8 * PAGE, 3, np.uint8)
    )
    cluster.close()


def test_metadata_transient_blip_absorbed_by_bounded_retry():
    """One flaky shard RPC is absorbed by the retry layer: counted in
    ``metadata_retries``, each backoff drawn from the bounded policy, and the
    shard's health returns to live on the retried success."""
    slept = []
    policy = RetryPolicy(max_attempts=3, sleep=slept.append)
    cluster = Cluster(
        n_data_providers=2, n_metadata_providers=4, metadata_replication=2,
        shared_cache_bytes=0, retry_policy=policy,
    )
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(8 * PAGE, PAGE)
    data = np.arange(8 * PAGE, dtype=np.uint8)
    v = handle.write(data, 0)
    shard = cluster.metadata.shards[0]
    real_get_many = shard.get_many
    blips = {"left": 1}

    def flaky_get_many(keys):
        if blips["left"]:
            blips["left"] -= 1
            raise ProviderFailed("injected metadata blip")
        return real_get_many(keys)

    shard.get_many = flaky_get_many
    before = len(slept)
    np.testing.assert_array_equal(
        sess.open(handle.blob_id).read(0, 8 * PAGE, version=v).data, data
    )
    shard.get_many = real_get_many
    assert cluster.stats.metadata_retries >= 1
    new_sleeps = slept[before:]
    assert new_sleeps, "a retry must back off"
    bound = policy.max_delay_seconds * (1 + policy.jitter)
    assert all(0 <= s <= bound for s in new_sleeps)
    assert sum(new_sleeps) <= cluster.stats.metadata_retries * bound
    assert cluster.metadata.shard_health(0) == "live"  # success cleared it
    cluster.close()


def test_dead_metadata_shard_fails_fast_never_hangs_reads():
    """Acceptance: a dead shard replica never hangs a read. With the shard
    DECLARED dead the retry loop fails fast — the read completes through the
    surviving replica with ZERO backoff sleeps (asserted via the injected
    sleep, so the test itself never waits on wall clock)."""
    slept = []
    cluster = Cluster(
        n_data_providers=2, n_metadata_providers=4, metadata_replication=2,
        shared_cache_bytes=0,
        retry_policy=RetryPolicy(max_attempts=3, sleep=slept.append),
        health=HealthConfig(dead_after=2, clock=FakeClock()),
    )
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(16 * PAGE, PAGE)
    data = np.arange(16 * PAGE, dtype=np.uint8)
    v = handle.write(data, 0)
    cluster.metadata.fail_shard(0)
    cluster.metadata.note_shard_failure(0)
    cluster.metadata.note_shard_failure(0)  # -> declared dead
    assert cluster.metadata.dead_shards() == [0]
    before = len(slept)
    np.testing.assert_array_equal(
        sess.open(handle.blob_id).read(0, 16 * PAGE, version=v).data, data
    )
    assert slept[before:] == [], "dead shards must not burn the retry budget"
    cluster.close()


def test_wedged_metadata_shard_bounded_by_rpc_timeout():
    """A shard that answers arbitrarily slowly (wedged, not crashed) costs
    one bounded timeout per attempt instead of hanging the read plane."""
    cluster = Cluster(
        n_data_providers=2, n_metadata_providers=4, metadata_replication=2,
        shared_cache_bytes=0,
        retry_policy=RetryPolicy(max_attempts=1, sleep=lambda s: None),
        metadata_timeout_seconds=0.05,
    )
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(8 * PAGE, PAGE)
    data = np.arange(8 * PAGE, dtype=np.uint8)
    v = handle.write(data, 0)
    shard = cluster.metadata.shards[0]
    real_get_many = shard.get_many

    def wedged_get_many(keys):
        threading.Event().wait(0.3)  # far past the 50ms attempt budget
        return real_get_many(keys)

    shard.get_many = wedged_get_many
    np.testing.assert_array_equal(
        sess.open(handle.blob_id).read(0, 8 * PAGE, version=v).data, data
    )
    shard.get_many = real_get_many
    assert cluster.metadata.shard_health(0) in ("suspect", "dead")
    cluster.close()


def test_mid_writev_shard_kill_write_completes_and_repairs():
    """Tentpole mirror of the data-plane mid-flight death: a metadata shard
    that dies while its node batch is in flight does not abort the writev —
    the quorum rule publishes through the surviving replicas, and the repair
    pass rebuilds the dead replica's node set once the shard rejoins."""
    cluster = Cluster(
        n_data_providers=2, n_metadata_providers=4, metadata_replication=2,
        shared_cache_bytes=0,
        retry_policy=RetryPolicy(max_attempts=2, sleep=lambda s: None),
    )
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(8 * PAGE, PAGE)
    shard = cluster.metadata.shards[2]
    started, release = threading.Event(), threading.Event()
    real_put_many = shard.put_many

    def dying_put_many(nodes):
        started.set()
        assert release.wait(10)
        return real_put_many(nodes)

    shard.put_many = dying_put_many
    data = np.arange(8 * PAGE, dtype=np.uint8)
    versions = []
    t = threading.Thread(target=lambda: versions.append(handle.write(data, 0)))
    t.start()
    if not started.wait(5):
        # no node of this write homes on shard 2: kill it anyway — the write
        # must still complete untouched
        pass
    cluster.metadata.fail_shard(2)  # dies mid-flight (put raises on release)
    release.set()
    t.join(10)
    shard.put_many = real_put_many
    assert versions == [1], "write must publish despite the mid-flight death"
    np.testing.assert_array_equal(handle.read(0, 8 * PAGE, version=1).data, data)
    # rejoin + repair: the dead replica's journal-covered node set is rebuilt
    cluster.metadata.recover_shard(2)
    cluster.repair_service.run_once()
    blob = handle.blob_id
    published, aborted = cluster.version_manager.repair_horizon(blob)
    for key, node in cluster.metadata.iter_nodes(blob):
        if key.version > published or key.version in aborted:
            continue
        for sid in cluster.metadata._replica_ids(key):
            assert cluster.metadata.shards[sid].get(key) is not None, (
                f"replica {sid} missing {key} after repair"
            )
    cluster.close()


# --------------------------- page integrity (checksums) ------------------------


def test_page_checksum_detects_corruption():
    # the checksum is a position-weighted word sum (it replaced zlib.crc32
    # on the fetch hot path): deterministic across buffer types, catches
    # single-byte flips anywhere, catches word swaps (pure sums would not),
    # and handles non-word-aligned tails
    rng = np.random.default_rng(7)
    page = rng.integers(0, 256, 4 * PAGE, dtype=np.uint8)
    base = page_checksum(page)
    assert base == page_checksum(page.copy())
    assert base == page_checksum(page.tobytes())
    for i in (0, 1, page.size // 2, page.size - 1):
        flipped = page.copy()
        flipped[i] ^= 0x01
        assert page_checksum(flipped) != base
    swapped = page.copy()
    words = swapped.view(np.uint64)
    words[0], words[3] = words[3].copy(), words[0].copy()
    assert page_checksum(swapped) != base
    tail = page[:37]
    assert page_checksum(tail) == page_checksum(tail.tobytes())
    assert page_checksum(tail) != page_checksum(page[:36])


def test_leaf_checksums_computed_at_freeze_time():
    cluster = Cluster(n_data_providers=2, shared_cache_bytes=0)
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(4 * PAGE, PAGE)
    handle.write(np.arange(4 * PAGE, dtype=np.uint8), 0)
    pm = cluster.provider_manager
    leaves = 0
    for key, node in cluster.metadata.iter_nodes(handle.blob_id):
        if not node.is_leaf:
            continue
        leaves += 1
        assert node.checksum is not None
        pid, page_key = node.page
        assert page_checksum(pm.get_provider(pid).get_page(page_key)) == node.checksum
    assert leaves > 0
    cluster.close()


def _corrupt_stored_page(provider, page_key):
    bad = provider._pages[page_key].copy()
    bad[0] ^= 0xFF
    bad.flags.writeable = False
    provider._pages[page_key] = bad


def test_corrupt_page_read_falls_back_verifies_and_repairs():
    """Satellite: flip a byte in a stored page. The read must return the
    CORRECT bytes via a verified replica, count the checksum failure, and
    repair the corrupt copy in place."""
    cluster = Cluster(n_data_providers=3, page_replication=2,
                      shared_cache_bytes=0)
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(8 * PAGE, PAGE)
    data = np.random.default_rng(5).integers(0, 255, 8 * PAGE, dtype=np.uint8)
    v = handle.write(data, 0)
    # corrupt the PRIMARY copy of the first leaf
    target = None
    for key, node in cluster.metadata.iter_nodes(handle.blob_id):
        if node.is_leaf and node.key.offset == 0:
            target = node
            break
    assert target is not None
    pid, page_key = target.page
    provider = cluster.provider_manager.get_provider(pid)
    _corrupt_stored_page(provider, page_key)
    out = sess.open(handle.blob_id).read(0, 8 * PAGE, version=v).data
    np.testing.assert_array_equal(out, data)  # corruption never surfaces
    assert cluster.stats.checksum_failures >= 1
    assert cluster.stats.repaired_pages >= 1
    # the bad copy was overwritten with verified bytes
    assert page_checksum(provider._pages[page_key]) == target.checksum
    cluster.close()


def test_repair_skips_corrupt_survivor_as_source():
    """A corrupt copy must never become the repair SOURCE: re-replication
    verifies each survivor against the leaf checksum and copies only
    verified bytes onto the replacement provider."""
    cluster = Cluster(n_data_providers=4, page_replication=3,
                      shared_cache_bytes=0, health=HealthConfig(dead_after=1))
    pm = cluster.provider_manager
    pm.on_dead = None  # drive the pass by hand
    sess = cluster.session(cache_bytes=0)
    handle = sess.create(4 * PAGE, PAGE)
    data = np.random.default_rng(7).integers(0, 255, 4 * PAGE, dtype=np.uint8)
    v = handle.write(data, 0)
    # pick one leaf: corrupt its primary's copy, kill one replica holder
    target = next(
        node for _, node in cluster.metadata.iter_nodes(handle.blob_id)
        if node.is_leaf and node.key.offset == 0
    )
    (bad_pid, bad_key), victims = target.page, target.replicas
    _corrupt_stored_page(pm.get_provider(bad_pid), bad_key)
    dead_pid = victims[0][0]
    pm.fail_provider(dead_pid)
    pm.note_failure(dead_pid)
    repaired, _ = cluster.repair_service.run_once()
    assert repaired > 0
    assert cluster.stats.checksum_failures >= 1  # the corrupt source was seen
    # every fresh copy of that leaf verifies against the freeze-time checksum
    for key, node in cluster.metadata.iter_nodes(handle.blob_id):
        if not node.is_leaf or node.key != target.key:
            continue
        for pid, page_key in node.all_page_refs():
            if pid == bad_pid:
                continue  # still holds its corrupt copy (read path repairs it)
            assert page_checksum(pm.get_provider(pid).get_page(page_key)) \
                == node.checksum
    np.testing.assert_array_equal(
        sess.open(handle.blob_id).read(0, 4 * PAGE, version=v).data, data
    )
    cluster.close()


# --------------------------- chaos campaign threads ----------------------------


def _start_threads(targets, crashes):
    """Start one daemon thread per ``(fn, *args)``; a thread that raises
    appends its traceback to ``crashes`` instead of dying as a warning."""

    def run(fn, *args):
        try:
            fn(*args)
        except BaseException:
            crashes.append(traceback.format_exc())

    threads = [
        threading.Thread(target=run, args=target, daemon=True)
        for target in targets
    ]
    for t in threads:
        t.start()
    return threads


def _join_threads(threads, crashes, timeout):
    """Join ``threads``; fail on any still running or any that raised."""
    for t in threads:
        t.join(timeout)
    stuck = [t.name for t in threads if t.is_alive()]
    assert not stuck, f"campaign threads still running: {stuck}"
    assert not crashes, "a campaign thread raised:\n" + "\n".join(crashes)


# --------------------------- metadata chaos campaign ---------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chaos_metadata_faults_zero_published_data_loss(seed):
    """Satellite chaos campaign: mixed writer/reader traffic while a seeded
    schedule kills/drops/delays METADATA shards (at most 1 of each node's 2
    replicas at a time) alongside light data-plane faults. Published
    versions must lose nothing, the frontier must stay monotone, and after
    drain + repair every journal-covered node is back on ALL its replica
    shards."""
    n_shards, meta_replication = 4, 2
    cluster = Cluster(
        n_data_providers=4, page_replication=2,
        n_metadata_providers=n_shards, metadata_replication=meta_replication,
        shared_cache_bytes=0,
        retry_policy=RetryPolicy(max_attempts=3, base_delay_seconds=0.001,
                                 max_delay_seconds=0.004),
        health=HealthConfig(dead_after=2, window_seconds=60.0),
    )
    writer_sessions = [cluster.session(cache_bytes=0) for _ in range(2)]
    blob = writer_sessions[0].create(64 * PAGE, PAGE).blob_id
    meta_faults = FaultSchedule.generate(
        seed=seed, n_providers=n_shards, n_events=8, max_dead=1,
        min_gap=3, max_gap=20, target=METADATA,
    )
    data_faults = FaultSchedule.generate(
        seed=seed + 100, n_providers=4, n_events=4, max_dead=1,
        min_gap=10, max_gap=40,
    )
    injector = FaultInjector(
        cluster, FaultSchedule(meta_faults.events + data_faults.events)
    )
    injector.attach()

    published = []
    published_lock = threading.Lock()
    errors = []
    n_rounds, regions = 8, 4

    def writer(wid, sess):
        handle = sess.open(blob)
        fill = 1
        for r in range(n_rounds):
            region = (wid * regions + r % regions) * 8
            value = (wid * 100 + fill) % 251 + 1
            fill += 1
            try:
                v = handle.write(
                    np.full(8 * PAGE, value, np.uint8), region * PAGE
                )
            except ProviderFailed:
                continue  # clean abort (quorum loss at that instant)
            with published_lock:
                published.append((v, region, 8, value))

    def reader():
        sess = cluster.session(cache_bytes=0)
        handle = sess.open(blob)
        last = 0
        for _ in range(30):
            v = handle.latest_published()
            assert v >= last, "publish frontier must be monotone"
            last = v
            if v:
                try:
                    handle.read(0, 64 * PAGE, version=v)
                except ProviderFailed as err:  # pragma: no cover
                    errors.append(err)
            threading.Event().wait(0.002)

    crashes = []
    threads = _start_threads(
        [(writer, i, s) for i, s in enumerate(writer_sessions)]
        + [(reader,)] * 2,
        crashes,
    )
    _join_threads(threads, crashes, 60)
    assert not errors, f"reads failed under metadata chaos: {errors[:3]}"

    injector.drain()
    injector.detach()
    cluster.repair_service.run_once()

    checker = cluster.session(cache_bytes=0).open(blob)
    latest = checker.latest_published()
    for v, region, n, value in published:
        np.testing.assert_array_equal(
            checker.read(region * PAGE, n * PAGE, version=v).data,
            np.full(n * PAGE, value, np.uint8),
            err_msg=f"seed {seed}: version {v} lost data",
        )
    expected = np.zeros(64 * PAGE, np.uint8)
    for v, region, n, value in sorted(published):
        if v <= latest:
            expected[region * PAGE:(region + n) * PAGE] = value
    np.testing.assert_array_equal(
        checker.read(0, 64 * PAGE, version=latest).data, expected
    )
    # metadata replication restored: every journal-covered node on ALL homes
    published_frontier, aborted = cluster.version_manager.repair_horizon(blob)
    for key, node in cluster.metadata.iter_nodes(blob):
        if key.version > published_frontier or key.version in aborted:
            continue
        for sid in cluster.metadata._replica_ids(key):
            assert cluster.metadata.shards[sid].get(key) is not None, (
                f"seed {seed}: replica {sid} missing {key} after repair"
            )
    cluster.close()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chaos_mixed_traffic_zero_published_data_loss(seed):
    """THE acceptance chaos test: 8 providers, 3-way replication, live mixed
    traffic from multiple writer+reader sessions while a seeded schedule
    kills up to 2 providers at a time (and injects drops/delays). Published
    versions must lose nothing, reads must all complete, the frontier must
    be monotone, and repair must restore full replication after recovery."""
    n_providers, replication = 8, 3
    cluster = Cluster(
        n_data_providers=n_providers, page_replication=replication,
        shared_cache_bytes=0,
        retry_policy=RetryPolicy(max_attempts=3, base_delay_seconds=0.001,
                                 max_delay_seconds=0.004),
        health=HealthConfig(dead_after=2, window_seconds=60.0),
    )
    writer_sessions = [cluster.session(cache_bytes=0) for _ in range(2)]
    blob = writer_sessions[0].create(64 * PAGE, PAGE).blob_id
    schedule = FaultSchedule.generate(
        seed=seed, n_providers=n_providers, n_events=10, max_dead=2,
        min_gap=3, max_gap=25,
    )
    injector = FaultInjector(cluster, schedule)
    injector.attach()

    published = []  # (version, page_offset, n_pages, fill) — the oracle
    published_lock = threading.Lock()
    frontiers = []
    errors = []
    n_rounds, regions = 8, 4  # each writer owns `regions` disjoint regions

    def writer(wid, sess):
        handle = sess.open(blob)
        fill = 1
        for r in range(n_rounds):
            region = (wid * regions + r % regions) * 8  # 8-page regions
            value = (wid * 100 + fill) % 251 + 1
            fill += 1
            try:
                v = handle.write(
                    np.full(8 * PAGE, value, np.uint8), region * PAGE
                )
            except ProviderFailed:
                continue  # aborted cleanly (no healthy target at that instant)
            with published_lock:
                published.append((v, region, 8, value))

    def reader():
        sess = cluster.session(cache_bytes=0)
        handle = sess.open(blob)
        last = 0
        for _ in range(30):
            v = handle.latest_published()
            assert v >= last, "publish frontier must be monotone"
            frontiers.append(v)
            last = v
            if v:
                try:
                    handle.read(0, 64 * PAGE, version=v)
                except ProviderFailed as err:  # pragma: no cover - must not happen
                    errors.append(err)
            threading.Event().wait(0.002)

    crashes = []
    threads = _start_threads(
        [(writer, i, s) for i, s in enumerate(writer_sessions)]
        + [(reader,)] * 2,
        crashes,
    )
    _join_threads(threads, crashes, 60)
    assert not errors, f"reads failed under chaos: {errors[:3]}"

    injector.drain()   # recover any provider still down
    injector.detach()
    repaired, scrubbed = cluster.repair_service.run_once()

    # -- zero data loss: every published write is byte-exact from providers
    checker = cluster.session(cache_bytes=0).open(blob)
    latest = checker.latest_published()
    for v, region, n, value in published:
        out = checker.read(region * PAGE, n * PAGE, version=v).data
        np.testing.assert_array_equal(
            out, np.full(n * PAGE, value, np.uint8),
            err_msg=f"seed {seed}: version {v} lost data",
        )
    # -- the full blob at the frontier matches the newest write per region
    expected = np.zeros(64 * PAGE, np.uint8)
    for v, region, n, value in sorted(published):
        if v <= latest:
            expected[region * PAGE:(region + n) * PAGE] = value
    np.testing.assert_array_equal(
        checker.read(0, 64 * PAGE, version=latest).data, expected
    )
    # -- replication factor restored on every published leaf
    pm = cluster.provider_manager
    aborted = cluster.version_manager.aborted_view(blob)
    for key, node in cluster.metadata.iter_nodes(blob):
        if not node.is_leaf or key.version > latest or key.version in aborted:
            continue
        refs = node.all_page_refs()
        pids = {pid for pid, _ in refs}
        # >= not ==: the replica balancer may have promoted hot pages to
        # EXTRA replicas under the reader traffic, which is fine
        assert len(pids) >= replication, (
            f"seed {seed}: leaf {key} under-replicated after repair: {refs}"
        )
        for pid, page_key in refs:
            provider = pm.get_provider(pid)
            assert not provider.failed
            assert provider.has_page(page_key)
    cluster.close()


# ------------------------------ node-plane chaos campaign ----------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chaos_node_faults_zero_published_data_loss(seed):
    """THE federated acceptance chaos test: 4 nodes x 16 sessions of mixed
    traffic over one shared substrate while a seeded node-plane schedule
    kills / partitions / wedges whole nodes and a concurrent GC thread runs
    federated epoch/lease passes. Invariants (interleaving-independent):
    zero published-data loss for every version no GC pass was entitled to
    drop (at or below the frontier that pass swept and not kept), a monotone
    publish frontier on every node, and the lease invariant — after the
    final pass no node's cache tier holds a collected version."""
    n_nodes, writers_per_node, readers_per_node = 4, 2, 2
    fed = Federation(
        n_nodes=n_nodes,
        n_data_providers=4, page_replication=2,
        n_metadata_providers=4, metadata_replication=2,
        lease_seconds=0.05,
        retry_policy=RetryPolicy(max_attempts=2, base_delay_seconds=0.001,
                                 max_delay_seconds=0.004),
        health=HealthConfig(dead_after=2, window_seconds=60.0),
    )
    sessions = [
        [fed.nodes[n].session() for _ in range(writers_per_node + readers_per_node)]
        for n in range(n_nodes)
    ]
    assert sum(len(s) for s in sessions) == 16
    blob = sessions[0][0].create(64 * PAGE, PAGE).blob_id

    schedule = FaultSchedule.generate(
        seed=seed, n_providers=n_nodes, n_events=10, max_dead=2,
        min_gap=5, max_gap=30, target=NODE,
    )
    injector = FaultInjector(fed, schedule)
    injector.attach()

    published = []  # (version, region, value) oracle, appended post-ack only
    published_lock = threading.Lock()
    errors = []
    gc_passes = []  # (keep, frontier) of each mid-campaign GC pass
    stop_gc = threading.Event()
    n_rounds = 6

    def writer(node_i, slot, sess):
        wid = node_i * writers_per_node + slot
        handle = sess.open(blob)
        region = wid * 8  # each writer owns its 8-page region
        for r in range(n_rounds):
            value = (wid * 31 + r) % 251 + 1
            try:
                v = handle.write(np.full(8 * PAGE, value, np.uint8),
                                 region * PAGE)
            except (ProviderFailed, ValueError):
                continue  # node down / writer recovered: never acked
            with published_lock:
                published.append((v, region, value))

    def reader(node_i, sess):
        handle = sess.open(blob)
        last = 0
        for _ in range(20):
            v = handle.latest_published()
            assert v >= last, "publish frontier must be monotone"
            last = v
            try:
                snap = handle.at(None)  # federated pin: GC must honor it
            except (ProviderFailed, ValueError):
                threading.Event().wait(0.002)
                continue  # node down or partitioned: pin safely refused
            try:
                data = snap.read(0, 64 * PAGE)
                for w in range(n_nodes * writers_per_node):
                    region = data[w * 8 * PAGE:(w + 1) * 8 * PAGE]
                    vals = set(np.unique(region).tolist())
                    if len(vals) > 1:
                        errors.append(
                            f"torn region {w} at v{snap.version}: {sorted(vals)}"
                        )
            except ProviderFailed:
                pass  # node died mid-read: acceptable, data loss is not
            finally:
                snap.release()
            threading.Event().wait(0.002)

    def gc_loop():
        while not stop_gc.wait(0.02):
            keep = fed.version_manager.latest_published(blob)
            if keep:
                _, _, frontier = fed.gc(blob, keep_versions=[keep])
                gc_passes.append((keep, frontier))

    crashes = []
    traffic = _start_threads(
        [(writer, n, s, sessions[n][s])
         for n in range(n_nodes) for s in range(writers_per_node)]
        + [(reader, n, sessions[n][writers_per_node + s])
           for n in range(n_nodes) for s in range(readers_per_node)],
        crashes,
    )
    gc_thread = _start_threads([(gc_loop,)], crashes)
    _join_threads(traffic, crashes, 120)
    stop_gc.set()
    _join_threads(gc_thread, crashes, 120)
    assert not errors, f"seed {seed}: stale/torn reads: {errors[:3]}"

    injector.drain()  # recover_all rejoins every downed node
    injector.detach()
    fed.repair_service.run_once()
    assert all(fed.node_mode(i) == "up" for i in range(n_nodes))

    # -- zero published-data loss: every acked write no GC pass was
    #    entitled to collect (a pass keeps what it was asked to plus the
    #    frontier it swept; every older published version may go)
    checker = fed.nodes[1].session(cache_bytes=0).open(blob)
    latest = checker.latest_published()
    for v, region, value in published:
        if any(v < frontier and v != keep for keep, frontier in gc_passes):
            continue
        np.testing.assert_array_equal(
            checker.read(region * PAGE, 8 * PAGE, version=v).data,
            np.full(8 * PAGE, value, np.uint8),
            err_msg=f"seed {seed}: version {v} lost data",
        )
    # -- the frontier composite matches the newest surviving write per region
    expected = np.zeros(64 * PAGE, np.uint8)
    for v, region, value in sorted(published):
        if v <= latest:
            expected[region * PAGE:(region + 8) * PAGE] = value
    np.testing.assert_array_equal(
        checker.read(0, 64 * PAGE, version=latest).data, expected
    )

    # -- lease invariant: after a final federated pass, no node's shared
    #    tier holds a collected version (every live node acked the epoch)
    fed.gc(blob, keep_versions=[latest])
    for i in range(n_nodes):
        for cached_v in fed.nodes[i].shared_cache.cached_versions(blob):
            assert cached_v == 0 or cached_v >= latest, (
                f"seed {seed}: node {i} caches collected v{cached_v}"
            )
    assert fed.coordinator.epoch() > 1
    fed.close()
