"""Unit + property tests for the faithful blob-store reproduction, driven
through the layered Cluster / Session / BlobHandle API."""

import threading

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    Cluster,
    ZERO_VERSION,
    compute_border_links,
    count_write_nodes,
)

PAGE = 64  # tiny pages for tests


def make_cluster(**kw):
    kw.setdefault("n_data_providers", 4)
    kw.setdefault("n_metadata_providers", 4)
    kw.setdefault("shared_cache_bytes", 0)
    return Cluster(**kw)


def test_alloc_read_zero_version():
    handle = make_cluster().session().create(16 * PAGE, PAGE)
    res = handle.read(0, 16 * PAGE)
    assert res.latest_published == ZERO_VERSION
    assert not res.data.any()  # version 0 is the all-zero string (paper §II)


def test_write_then_read_roundtrip():
    handle = make_cluster().session().create(16 * PAGE, PAGE)
    payload = np.arange(4 * PAGE, dtype=np.uint8)
    v = handle.write(payload, 2 * PAGE)
    assert v == 1
    res = handle.read(2 * PAGE, 4 * PAGE, version=v)
    np.testing.assert_array_equal(res.data, payload)
    # untouched pages still zero
    assert not handle.read(0, 2 * PAGE, version=v).data.any()
    assert not handle.read(6 * PAGE, 10 * PAGE, version=v).data.any()


def test_versioning_snapshots_stay_readable():
    handle = make_cluster().session().create(8 * PAGE, PAGE)
    a = np.full(2 * PAGE, 7, dtype=np.uint8)
    b = np.full(2 * PAGE, 9, dtype=np.uint8)
    v1 = handle.write(a, 0)
    v2 = handle.write(b, PAGE)  # overlapping patch
    assert (v1, v2) == (1, 2)
    # v1 unchanged by the later overlapping write (COW)
    np.testing.assert_array_equal(handle.read(0, 2 * PAGE, version=v1).data, a)
    # v2 = v1 patched by b at offset PAGE
    expect = np.zeros(8 * PAGE, dtype=np.uint8)
    expect[: 2 * PAGE] = a
    expect[PAGE : 3 * PAGE] = b
    np.testing.assert_array_equal(
        handle.read(0, 8 * PAGE, version=v2).data, expect[: 8 * PAGE]
    )


def test_read_unpublished_version_fails():
    handle = make_cluster().session().create(4 * PAGE, PAGE)
    with pytest.raises(ValueError, match="not yet published"):
        handle.read(0, PAGE, version=1)


def test_unaligned_write_rejected():
    handle = make_cluster().session().create(4 * PAGE, PAGE)
    with pytest.raises(ValueError, match="page-aligned"):
        handle.write(np.zeros(PAGE, np.uint8), 3)


def test_metadata_sharing_between_versions():
    """COW weaving shares all unmodified subtrees (paper §III.C)."""
    cluster = make_cluster()
    handle = cluster.session().create(1024 * PAGE, PAGE)
    handle.write(np.ones(1024 * PAGE, np.uint8), 0)
    n_after_full = cluster.metadata.total_nodes()
    handle.write(np.ones(PAGE, np.uint8), 512 * PAGE)
    n_after_patch = cluster.metadata.total_nodes()
    # one-page patch creates exactly the root-to-leaf path: log2(1024)+1 nodes
    assert n_after_patch - n_after_full == 11
    assert n_after_patch - n_after_full == count_write_nodes(1024, 512, 1)


def test_page_replication_survives_provider_failure():
    cluster = make_cluster(n_data_providers=4, page_replication=2)
    handle = cluster.session().create(8 * PAGE, PAGE)
    payload = np.arange(8 * PAGE, dtype=np.uint8)
    v = handle.write(payload, 0)
    # kill the primary of some page: every page must still be readable
    cluster.provider_manager.fail_provider(0)
    np.testing.assert_array_equal(handle.read(0, 8 * PAGE, version=v).data, payload)


def test_metadata_replication_survives_shard_failure():
    cluster = make_cluster(n_metadata_providers=4, metadata_replication=2)
    handle = cluster.session().create(8 * PAGE, PAGE)
    payload = np.arange(8 * PAGE, dtype=np.uint8)
    v = handle.write(payload, 0)
    cluster.metadata.fail_shard(1)
    np.testing.assert_array_equal(handle.read(0, 8 * PAGE, version=v).data, payload)


def test_gc_keeps_reachable_shared_pages():
    cluster = make_cluster()
    handle = cluster.session().create(16 * PAGE, PAGE)
    base = np.ones(16 * PAGE, np.uint8)
    handle.write(base, 0)  # v1
    patch = np.full(PAGE, 5, np.uint8)
    handle.write(patch, 4 * PAGE)  # v2 shares 15 pages with v1
    nodes_freed, pages_freed, frontier = cluster.gc(
        handle.blob_id, keep_versions=[2]
    )
    assert frontier == 2
    assert pages_freed == 1  # only v1's overwritten page dies
    assert nodes_freed > 0  # v1's root path dies
    expect = base.copy()
    expect[4 * PAGE : 5 * PAGE] = patch
    np.testing.assert_array_equal(handle.read(0, 16 * PAGE, version=2).data, expect)


def test_gc_keeps_what_an_inflight_write_weaves_onto():
    """Regression: a write in flight during a GC pass has woven its tree
    onto the newest published version; a pass asked to keep only an older
    version must still keep what the in-flight write links to."""
    cluster = make_cluster()
    handle = cluster.session().create(16 * PAGE, PAGE)
    base = np.ones(16 * PAGE, np.uint8)
    handle.write(base, 0)  # v1
    handle.write(np.full(4 * PAGE, 2, np.uint8), 8 * PAGE)  # v2: the frontier
    held, release = threading.Event(), threading.Event()

    def gate(op, shard_id):
        if op == "put_many" and not held.is_set():
            held.set()  # the write has its version and its border links
            release.wait(10.0)

    for shard in cluster.metadata.shards:
        shard.fault_gate = gate
    writer = cluster.session().open(handle.blob_id)
    patch = np.full(PAGE, 3, np.uint8)
    versions = []
    t = threading.Thread(
        target=lambda: versions.append(writer.write(patch, 0)), daemon=True
    )
    t.start()
    assert held.wait(10.0)
    cluster.gc(handle.blob_id, keep_versions=[1])
    release.set()
    t.join(10.0)
    assert not t.is_alive() and versions == [3]
    expect = base.copy()
    expect[8 * PAGE : 12 * PAGE] = 2
    expect[:PAGE] = 3
    np.testing.assert_array_equal(
        handle.read(0, 16 * PAGE, version=3).data, expect
    )


def test_elastic_provider_join():
    cluster = make_cluster(n_data_providers=2)
    handle = cluster.session().create(8 * PAGE, PAGE)
    handle.write(np.ones(4 * PAGE, np.uint8), 0)
    new_pid = cluster.add_data_provider()
    handle.write(np.ones(4 * PAGE, np.uint8), 4 * PAGE)
    # the new provider picked up load (least-loaded placement)
    assert cluster.provider_manager.get_provider(new_pid).n_pages > 0


def test_version_manager_recovery_with_orphans():
    cluster = make_cluster()
    handle = cluster.session().create(8 * PAGE, PAGE)
    blob = handle.blob_id
    handle.write(np.ones(PAGE, np.uint8), 0)  # v1 complete
    # simulate a writer that got v2 assigned and crashed before reporting
    cluster.version_manager.assign_version(blob, 2, 1)
    handle.write(np.ones(PAGE, np.uint8), 4 * PAGE)  # v3 complete
    from repro.core import VersionManager

    vm2, orphans = VersionManager.recover(cluster.version_manager.journal)
    assert vm2.latest_published(blob) == 1  # publish stops before the orphan
    assert orphans[blob] == [2]
    # v3 completed: it publishes as soon as the orphan is resolved
    vm2.report_success(blob, 2)
    assert vm2.latest_published(blob) == 3


# ----------------------------- property tests --------------------------------


@st.composite
def patch_sequences(draw):
    n_pages = draw(st.sampled_from([8, 16, 32]))
    n_writes = draw(st.integers(min_value=1, max_value=8))
    writes = []
    for _ in range(n_writes):
        off = draw(st.integers(min_value=0, max_value=n_pages - 1))
        size = draw(st.integers(min_value=1, max_value=n_pages - off))
        fill = draw(st.integers(min_value=1, max_value=255))
        writes.append((off, size, fill))
    return n_pages, writes


@settings(max_examples=30, deadline=None)
@given(patch_sequences())
def test_serializability_reads_equal_prefix_of_patches(seq):
    """Paper §II: READ of version v == successive application of the first v
    patches to the all-zero string — for EVERY published version."""
    n_pages, writes = seq
    handle = make_cluster().session().create(n_pages * PAGE, PAGE)
    oracle = np.zeros(n_pages * PAGE, dtype=np.uint8)
    snapshots = [oracle.copy()]
    for off, size, fill in writes:
        buf = np.full(size * PAGE, fill, dtype=np.uint8)
        handle.write(buf, off * PAGE)
        oracle[off * PAGE : (off + size) * PAGE] = buf
        snapshots.append(oracle.copy())
    for v, snap in enumerate(snapshots):
        got = handle.read(0, n_pages * PAGE, version=v).data
        np.testing.assert_array_equal(got, snap)


@settings(max_examples=30, deadline=None)
@given(patch_sequences())
def test_border_links_point_to_latest_intersecting_version(seq):
    """compute_border_links must weave to the most recent intersecting patch."""
    n_pages, writes = seq
    intervals = {}

    for v, (off, size, _) in enumerate(writes, start=1):

        def version_of_segment(o, s):
            best = ZERO_VERSION
            for pv, (po, ps) in intervals.items():
                if po < o + s and o < po + ps:
                    best = max(best, pv)
            return best

        links = compute_border_links(n_pages, off, size, version_of_segment)
        for link in links:
            # the missing child never intersects the current patch
            assert not (link.child_offset < off + size and off < link.child_offset + link.child_size)
            assert link.child_version == version_of_segment(link.child_offset, link.child_size)
        intervals[v] = (off, size)


def test_unaligned_write_read_modify_write():
    handle = make_cluster().session().create(16 * PAGE, PAGE)
    base = np.arange(16 * PAGE, dtype=np.uint8)
    handle.write(base, 0)
    patch = np.full(PAGE, 200, np.uint8)
    off = 3 * PAGE + 17  # crosses two pages, unaligned both sides
    v = handle.write_unaligned(patch, off)
    expect = base.copy()
    expect[off : off + PAGE] = patch
    got = handle.read(0, 16 * PAGE, version=v).data
    np.testing.assert_array_equal(got, expect)
    # the pre-patch version is untouched (COW)
    np.testing.assert_array_equal(
        handle.read(0, 16 * PAGE, version=v - 1).data, base
    )
