"""Federated multi-node clusters: the GC epoch/lease protocol, fencing,
node death, and the version-abandon wakeup satellites.

Every lease test injects a fake clock into the coordinator AND the retry
policy's sleep, so lease expiry, renew-under-GC races and lease wait-outs
are driven deterministically — no wall-clock sleeps, no flakes.
"""

import threading

import numpy as np
import pytest

from repro.core import (
    Federation,
    GcEpochCoordinator,
    HealthConfig,
    ProviderFailed,
    RetryPolicy,
    VersionAbandoned,
    VersionManager,
    VersionWatch,
)

PAGE = 256
PAGES = 8


class FakeClock:
    def __init__(self, start=1000.0):
        self.now = start

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


def make_fed(clock, n_nodes=2, lease_seconds=10.0, dead_after=100):
    return Federation(
        n_nodes=n_nodes,
        n_data_providers=2,
        n_metadata_providers=2,
        max_workers=2,
        lease_seconds=lease_seconds,
        clock=clock,
        retry_policy=RetryPolicy(max_attempts=1, sleep=clock.advance),
        health=HealthConfig(dead_after=dead_after, window_seconds=1e9,
                            clock=clock),
    )


def fill(value, n_bytes=PAGE * PAGES):
    return np.full(n_bytes, value, np.uint8)


# ------------------------------ shared substrate -------------------------------


def test_cross_node_read_your_publishes():
    clock = FakeClock()
    fed = make_fed(clock)
    s0 = fed.nodes[0].session()
    s1 = fed.nodes[1].session()
    h0 = s0.create(PAGE * PAGES, PAGE)
    v1 = h0.write(fill(1), 0)
    assert h0.wait_for_version(v1, timeout=5.0)
    h1 = s1.open(h0.blob_id)
    np.testing.assert_array_equal(h1.read(0, PAGE * PAGES).data, fill(1))
    # both nodes share ONE frontier: a publish on node 1 is node 0's too
    v2 = h1.write(fill(2), 0)
    assert h0.wait_for_version(v2, timeout=5.0)
    np.testing.assert_array_equal(h0.read(0, PAGE * PAGES).data, fill(2))
    fed.close()


# ------------------------------ lease fencing ----------------------------------


def test_lease_expiry_fences_before_next_cache_serve():
    """The fencing invariant, deterministically: a node whose lease lapses
    while partitioned purges its cache tiers BEFORE the next cache serve and
    reads through to the providers — it can never serve a page federated GC
    may have reclaimed behind its back."""
    clock = FakeClock()
    fed = make_fed(clock, lease_seconds=10.0)
    s0 = fed.nodes[0].session()
    s1 = fed.nodes[1].session(cache_bytes=0)  # fills land in node 1's shared tier
    h0 = s0.create(PAGE * PAGES, PAGE)
    v1 = h0.write(fill(1), 0)
    h1 = s1.open(h0.blob_id)
    np.testing.assert_array_equal(h1.read(0, PAGE * PAGES).data, fill(1))
    assert fed.nodes[1].shared_cache.cached_versions(h0.blob_id) == [v1]

    fed.apply_node_fault(1, "partition")
    clock.advance(11.0)  # the lease expires mid-life, no renewal possible
    assert not fed.coordinator.lease_valid(1)
    # next read: fence FIRST (purge), then read through — still correct
    np.testing.assert_array_equal(h1.read(0, PAGE * PAGES).data, fill(1))
    assert fed.node_fenced(1)
    assert fed.nodes[1].shared_cache.cached_versions(h0.blob_id) == []
    assert fed.nodes[1].stats.lease_fences == 1
    assert fed.stats.lease_fences == 1
    # further fenced reads do not re-purge (one fence per transition) and
    # never fill the tiers
    np.testing.assert_array_equal(h1.read(0, PAGE * PAGES).data, fill(1))
    assert fed.nodes[1].stats.lease_fences == 1
    assert fed.nodes[1].shared_cache.cached_versions(h0.blob_id) == []

    fed.apply_node_fault(1, "recover")
    assert not fed.node_fenced(1)
    assert fed.coordinator.lease_valid(1)
    np.testing.assert_array_equal(h1.read(0, PAGE * PAGES).data, fill(1))
    assert fed.nodes[1].shared_cache.cached_versions(h0.blob_id) == [v1]
    fed.close()


def test_renew_under_gc_fences_and_rejoins_as_ack():
    """The renew-under-GC race: a renewal that discovers the epoch advanced
    underneath the lease must fence (purge) and rejoin at the current epoch
    — which IS the ack the GC pass waits for."""
    clock = FakeClock()
    fed = make_fed(clock, lease_seconds=10.0)
    s1 = fed.nodes[1].session(cache_bytes=0)
    h1 = s1.create(PAGE * PAGES, PAGE)
    h1.write(fill(1), 0)
    np.testing.assert_array_equal(h1.read(0, PAGE * PAGES).data, fill(1))

    # near expiry with a matching epoch: the guard renews inline, no fence
    clock.advance(6.0)
    np.testing.assert_array_equal(h1.read(0, PAGE * PAGES).data, fill(1))
    assert fed.coordinator.seconds_until_expiry(1) == 10.0
    assert fed.nodes[1].stats.lease_fences == 0

    # an epoch advances under the lease (a GC pass elsewhere): the next
    # near-expiry renewal fails, fences, and rejoins at the new epoch
    epoch = fed.coordinator.advance_epoch()
    clock.advance(6.0)
    np.testing.assert_array_equal(h1.read(0, PAGE * PAGES).data, fill(1))
    assert fed.nodes[1].stats.lease_fences == 1
    assert fed.coordinator.joined_epoch(1) == epoch
    assert not fed.node_fenced(1)  # rejoined: serving again from empty tiers
    fed.close()


def test_gc_waits_out_partitioned_nodes_lease_and_records_stall():
    """A partitioned node cannot ack: the GC pass stalls until the node's
    lease expires (counted in epoch_stalls), then reclaims safely — the
    expired node fences before it could ever serve a collected page."""
    clock = FakeClock()
    fed = make_fed(clock, lease_seconds=10.0)
    s0 = fed.nodes[0].session()
    s1 = fed.nodes[1].session(cache_bytes=0)
    h0 = s0.create(PAGE * PAGES, PAGE)
    v1 = h0.write(fill(1), 0)
    h1 = s1.open(h0.blob_id)
    h1.read(0, PAGE * PAGES)  # node 1 caches v1's pages
    v2 = h0.write(fill(2), 0)

    fed.apply_node_fault(1, "partition")
    epoch_before = fed.coordinator.epoch()
    fed.gc(h0.blob_id, keep_versions=[v2])  # wait-out runs on the fake clock
    assert fed.coordinator.epoch() == epoch_before + 1
    assert fed.stats.epoch_stalls == 1
    assert not fed.coordinator.lease_valid(1)  # reclaimed only past expiry
    # v1 is gone from storage; the partitioned node's NEXT serve fences, so
    # its stale v1 pages can never be observed
    np.testing.assert_array_equal(h1.read(0, PAGE * PAGES).data, fill(2))
    assert fed.node_fenced(1)
    assert fed.nodes[1].shared_cache.cached_versions(h0.blob_id) == []
    with pytest.raises(KeyError):
        h1.read(0, PAGE * PAGES, version=v1)
    fed.close()


def test_federated_gc_honors_other_nodes_snapshot_pins():
    clock = FakeClock()
    fed = make_fed(clock)
    s0 = fed.nodes[0].session()
    s1 = fed.nodes[1].session()
    h0 = s0.create(PAGE * PAGES, PAGE)
    v1 = h0.write(fill(1), 0)
    h1 = s1.open(h0.blob_id)
    snap = h1.at(v1)  # node 1 pins v1 at the coordinator
    v2 = h0.write(fill(2), 0)
    fed.nodes[0].gc(h0.blob_id, keep_versions=[v2])
    # the other node's pin vetoed v1's reclaim
    np.testing.assert_array_equal(snap.read(0, PAGE * PAGES), fill(1))
    snap.release()
    assert fed.coordinator.pinned_versions(h0.blob_id) == set()
    fed.gc(h0.blob_id, keep_versions=[v2])
    with pytest.raises(KeyError):
        h1.read(0, PAGE * PAGES, version=v1)
    fed.close()


def test_partitioned_node_pin_refused_safely():
    clock = FakeClock()
    fed = make_fed(clock)
    s0 = fed.nodes[0].session()
    s1 = fed.nodes[1].session()
    h0 = s0.create(PAGE * PAGES, PAGE)
    v1 = h0.write(fill(1), 0)
    h1 = s1.open(h0.blob_id)
    fed.apply_node_fault(1, "partition")
    # a pin the coordinator cannot see would be silently ignored by GC —
    # refusing it is the only safe answer
    with pytest.raises(ProviderFailed):
        h1.at(v1)
    fed.apply_node_fault(1, "recover")
    snap = h1.at(v1)
    snap.release()
    fed.close()


def test_unpin_lost_while_unreachable_resyncs_on_rejoin():
    """A snapshot released while its node is down cannot deliver its unpin
    to the coordinator (best-effort, swallowed). Without the rejoin-time pin
    resync the coordinator would veto that version's reclaim forever."""
    clock = FakeClock()
    fed = make_fed(clock)
    s0 = fed.nodes[0].session()
    s1 = fed.nodes[1].session()
    h0 = s0.create(PAGE * PAGES, PAGE)
    v1 = h0.write(fill(1), 0)
    h1 = s1.open(h0.blob_id)
    snap = h1.at(v1)  # node 1 pins v1 at the coordinator
    fed.apply_node_fault(1, "kill")
    snap.release()  # the unpin RPC is lost with the node
    assert fed.coordinator.pinned_versions(h0.blob_id) == {v1}
    v2 = h0.write(fill(2), 0)
    fed.gc(h0.blob_id, keep_versions=[v2])
    # the leaked pin still vetoed this pass (conservative direction) ...
    np.testing.assert_array_equal(
        h0.read(0, PAGE * PAGES, version=v1).data, fill(1)
    )
    # ... but rejoin resyncs the coordinator to the node's local pin table
    fed.apply_node_fault(1, "recover")
    assert fed.coordinator.pinned_versions(h0.blob_id) == set()
    fed.gc(h0.blob_id, keep_versions=[v2])
    with pytest.raises(KeyError):
        h0.read(0, PAGE * PAGES, version=v1)
    fed.close()


# ------------------------------ node death -------------------------------------


def test_node_death_reclaims_lease_pins_and_recovers_writers():
    """A node declared dead mid-pass loses its lease and pins, and its
    sessions' assigned-but-unreported versions are abandoned so in-order
    publication never wedges behind the dead writers."""
    clock = FakeClock()
    fed = make_fed(clock, dead_after=1)
    vm = fed.version_manager
    s0 = fed.nodes[0].session()
    s1 = fed.nodes[1].session()
    h0 = s0.create(PAGE * PAGES, PAGE)
    v1 = h0.write(fill(1), 0)
    h1 = s1.open(h0.blob_id)
    snap = h1.at(v1)  # node 1 holds a pin the death must reclaim

    # node 1 has a write mid-flight: version assigned, success never reported
    (doomed, _links), = vm.assign_versions(h0.blob_id, [(0, PAGES)])
    with s1._async_lock:
        s1._inflight_versions.setdefault(h0.blob_id, set()).add(doomed)

    fed.apply_node_fault(1, "kill")
    fed.gc(h0.blob_id, keep_versions=[v1])  # one failed ack = death verdict
    assert fed.coordinator.node_dead(1)
    assert fed.coordinator.pinned_versions(h0.blob_id) == set()
    assert not fed.coordinator.lease_valid(1)
    # the dead writer's version became a hole: its number is never
    # reused, a late report from the writer fails, and the frontier
    # advances straight past it
    with pytest.raises(VersionAbandoned):
        vm.report_success(h0.blob_id, doomed)
    v_next = h0.write(fill(3), 0)
    assert v_next == doomed + 1
    assert vm.latest_published(h0.blob_id) == v_next

    fed.apply_node_fault(1, "recover")
    assert not fed.coordinator.node_dead(1)
    assert fed.coordinator.lease_valid(1)
    np.testing.assert_array_equal(h1.read(0, PAGE * PAGES).data, fill(3))
    snap.release()  # unpin after death is best-effort, must not raise
    fed.close()


def test_report_success_after_writer_recovery_raises_not_silent_loss():
    """A live-but-partitioned writer whose in-flight version a death verdict
    abandoned must see its write FAIL — silently acking a write that will
    never publish is data loss."""
    vm = VersionManager()
    blob = vm.alloc(PAGES, PAGE)
    v, _ = vm.assign_version(blob, 0, PAGES)
    vm.abandon(blob, [v])  # writer recovery runs while the writer is mid-put
    with pytest.raises(VersionAbandoned):
        vm.report_success(blob, v)


def test_abandoned_tail_version_number_is_never_reused():
    """Writer recovery may abandon the newest assignment of a writer that is
    still live; reissuing its number would let that writer's late report
    publish the next owner's version. The number stays a hole instead."""
    vm = VersionManager()
    blob = vm.alloc(PAGES, PAGE)
    v, _ = vm.assign_version(blob, 0, PAGES)
    vm.abandon(blob, [v])
    w, _ = vm.assign_version(blob, 0, PAGES)
    assert w == v + 1
    with pytest.raises(VersionAbandoned):
        vm.report_success(blob, v)  # the live writer's late report
    assert vm.latest_published(blob) == 0
    vm.report_success(blob, w)
    assert vm.latest_published(blob) == w


def test_snapshot_that_lost_its_pin_to_a_death_verdict_fails_cleanly():
    """A death verdict reclaims the node's pins, so a pass may collect a
    version the node still holds a snapshot of. Reading that snapshot after
    the node rejoins fails as a node fault (``ProviderFailed``), not as the
    missing metadata that signals lost data."""
    clock = FakeClock()
    fed = make_fed(clock, dead_after=1)
    h0 = fed.nodes[0].session().create(PAGE * PAGES, PAGE)
    v1 = h0.write(fill(1), 0)
    snap = fed.nodes[1].session(cache_bytes=0).open(h0.blob_id).at(v1)
    v2 = h0.write(fill(2), 0)
    fed.apply_node_fault(1, "partition")
    fed.gc(h0.blob_id, keep_versions=[v2])  # death verdict, v1 collected
    assert fed.coordinator.node_dead(1)
    fed.apply_node_fault(1, "recover")
    with pytest.raises(ProviderFailed):
        snap.read(0, PAGE * PAGES)
    snap.release()
    fed.close()


# ------------------------- version-abandon wakeups -----------------------------


def test_abandon_wakes_waiters_fail_fast():
    """Satellite bugfix: a waiter on an awaited version used to block its
    FULL timeout when the version was abandoned after the wait began — the
    abandon must wake it immediately with the aborted-version error."""
    vm = VersionManager()
    blob = vm.alloc(PAGES, PAGE)
    v1, _ = vm.assign_version(blob, 0, PAGES)
    v2, _ = vm.assign_version(blob, 0, PAGES)
    results = []

    def waiter():
        try:
            vm.wait_published(blob, v1, timeout=30.0)
            results.append("published")
        except VersionAbandoned:
            results.append("abandoned")

    t = threading.Thread(target=waiter)
    t.start()
    threading.Event().wait(0.05)  # the waiter is parked on the condition
    vm.abandon(blob, [v1])  # v2 assigned after it -> v1 is an aborted hole
    t.join(5.0)  # must wake NOW, not after the 30s timeout
    assert not t.is_alive()
    assert results == ["abandoned"]

    # abandoning the newest assignment fails its waiters the same way
    vm.abandon(blob, [v2])
    with pytest.raises(VersionAbandoned):
        vm.wait_published(blob, v2, timeout=30.0)


def test_version_watch_skips_holes_and_waits_for_reissued_slots():
    vm = VersionManager()
    blob = vm.alloc(PAGES, PAGE)
    watch = VersionWatch(vm, blob, start_version=0)
    v1, _ = vm.assign_version(blob, 0, PAGES)
    v2, _ = vm.assign_version(blob, 0, PAGES)
    vm.abandon(blob, [v1])  # hole: v2 was assigned after it
    vm.report_success(blob, v2)
    # the hole is stepped over without delivery; v2 arrives in order
    assert watch.next(timeout=5.0) == v2

    v3, _ = vm.assign_version(blob, 0, PAGES)
    vm.abandon(blob, [v3])  # the newest assignment: a hole, never reused
    got = []
    t = threading.Thread(target=lambda: got.append(watch.next(timeout=30.0)))
    t.start()
    threading.Event().wait(0.05)
    # the watch steps over the tail hole and waits for the next number
    v4, _ = vm.assign_version(blob, 0, PAGES)
    assert v4 == v3 + 1
    vm.report_success(blob, v4)
    t.join(5.0)
    assert not t.is_alive()
    assert got == [v4]


# ------------------------------ coordinator unit -------------------------------


def test_coordinator_lease_and_epoch_protocol():
    clock = FakeClock()
    coord = GcEpochCoordinator(lease_seconds=10.0, clock=clock)
    assert coord.join(0) == 1
    assert coord.lease_valid(0)
    clock.advance(6.0)
    assert coord.seconds_until_expiry(0) == 4.0
    assert coord.renew(0)  # epoch matches: extended
    assert coord.seconds_until_expiry(0) == 10.0
    epoch = coord.advance_epoch()
    assert not coord.renew(0)  # epoch mismatch: must fence + rejoin
    assert coord.lease_valid(0)  # but the old lease still blocks reclaim
    assert coord.join(0) == epoch
    clock.advance(11.0)
    assert not coord.lease_valid(0)
    assert not coord.renew(0)  # expired leases cannot be renewed


def _on_first_metadata_rpc(fed, thread, action):
    """Run ``action`` once, inside the first metadata RPC ``thread`` makes:
    for a thread running ``fed.gc`` that is the home node's sweep marking
    its first tree. Returns an event set when it fired."""
    fired = threading.Event()

    def gate(op, shard_id):
        if threading.current_thread() is thread and not fired.is_set():
            fired.set()
            action()

    for shard in fed.metadata.shards:
        shard.fault_gate = gate
    return fired


def test_coordinator_pins_block_during_sweep():
    """A pin on any node (resolve + register at the coordinator) waits out
    the home node's sweep: every node shares the home node's gc guard, so
    the pin lands strictly after the pass and its version stays readable."""
    clock = FakeClock()
    fed = make_fed(clock)
    h0 = fed.nodes[0].session().create(PAGE * PAGES, PAGE)
    h0.write(fill(1), 0)
    h1 = fed.nodes[1].session().open(h0.blob_id)
    release = threading.Event()
    gc_thread = threading.Thread(
        target=fed.gc, args=(h0.blob_id, [1]), daemon=True
    )
    in_sweep = _on_first_metadata_rpc(
        fed, gc_thread, lambda: release.wait(10.0)
    )
    gc_thread.start()
    assert in_sweep.wait(10.0)
    snaps = []
    pinner = threading.Thread(
        target=lambda: snaps.append(h1.at(None)), daemon=True
    )
    pinner.start()
    pinner.join(0.1)
    assert pinner.is_alive()  # parked while sweeping
    assert fed.coordinator.pinned_versions(h0.blob_id) == set()
    release.set()
    gc_thread.join(10.0)
    pinner.join(10.0)
    assert not gc_thread.is_alive() and not pinner.is_alive()
    assert fed.coordinator.pinned_versions(h0.blob_id) == {1}
    np.testing.assert_array_equal(snaps[0].read(0, PAGE * PAGES), fill(1))
    snaps[0].release()
    assert fed.coordinator.pinned_versions(h0.blob_id) == set()
    fed.close()


def test_rejoin_inside_the_sweep_does_not_deadlock():
    """Regression: a chaos harness applies a due node ``recover`` on the
    thread whose RPC reaches it — here the GC thread, inside its own sweep.
    The rejoin's pin resync must not wait for that sweep to close."""
    clock = FakeClock()
    fed = make_fed(clock)
    h0 = fed.nodes[0].session().create(PAGE * PAGES, PAGE)
    v1 = h0.write(fill(1), 0)
    fed.apply_node_fault(1, "kill")
    gc_thread = threading.Thread(
        target=fed.gc, args=(h0.blob_id, [v1]), daemon=True
    )
    rejoined = _on_first_metadata_rpc(
        fed, gc_thread, lambda: fed.rejoin_node(1)
    )
    gc_thread.start()
    gc_thread.join(10.0)
    assert rejoined.is_set()
    assert not gc_thread.is_alive(), "GC pass wedged on its own rejoin"
    assert fed.node_mode(1) == "up"
    fed.close()


def test_coordinator_death_is_sticky_until_revive():
    clock = FakeClock()
    coord = GcEpochCoordinator(
        lease_seconds=10.0, clock=clock,
        health=HealthConfig(dead_after=2, window_seconds=1e9, clock=clock),
    )
    coord.join(0)
    assert not coord.note_failure(0)
    assert coord.health_state(0) == "suspect"
    assert coord.note_failure(0)  # the death verdict fires exactly once
    assert not coord.note_failure(0)
    assert coord.node_dead(0)
    with pytest.raises(ProviderFailed):
        coord.join(0)  # dead nodes cannot sneak back in via join
    coord.revive(0)
    assert not coord.node_dead(0)
    assert coord.join(0) >= 1
