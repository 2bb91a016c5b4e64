"""The checkpointer's per-shard layout on a 2x2 mesh of CPU devices.

The cases run in a child process that has four host devices
(``--xla_force_host_platform_device_count=4``), so that they run whatever
device count the test worker's own JAX has; one child runs every case and
each case is reported as a test of its own.

    python tests/test_sharded_checkpoint.py   # the child: one JSON line
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import traceback
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
PAGE = 4096


# ------------------------------ in the child ------------------------------
def _child_cases():
    """The cases by name; imported in the child only, where JAX has four
    devices."""
    import hashlib
    import threading
    import time

    import jax
    import numpy as np
    from jax._src.array import ArrayImpl
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from repro.core import Cluster
    from repro.storage.checkpoint import BlobCheckpointer

    devices = jax.devices()
    assert len(devices) == 4, devices
    mesh = Mesh(np.array(devices).reshape(2, 2), ("x", "y"))

    def session():
        return Cluster(n_data_providers=4, n_metadata_providers=4, shared_cache_bytes=0).session()

    # leaves cut along different dimensions and over the axes in either
    # order, one cut over one axis only (two distinct shards, each on two
    # devices), a replicated 0-d leaf and a NumPy leaf; no shard a page multiple
    SPECS = {
        "big": ((4 * 70001,), np.float32, P(("x", "y"))),
        "cols": ((13, 4 * 257), np.float32, P(None, ("x", "y"))),
        "grid": ((6, 2 * 515, 3), np.float32, P("x", "y")),
        "half": ((2 * 4999,), np.float32, P("y")),
        "ints": ((8, 333), np.int32, P(("y", "x"))),
        "rows": ((4 * 1001, 7), np.float32, P(("x", "y"))),
        "step": ((), np.int32, P()),
    }
    DISTINCT = {"big": 4, "cols": 4, "grid": 4, "half": 2, "ints": 4, "rows": 4, "step": 1, "host": 1}

    def host_state(seed):
        rng = np.random.default_rng(seed)
        out = {}
        for name, (shape, dtype, _) in SPECS.items():
            if dtype == np.int32:
                out[name] = rng.integers(-(2**31), 2**31 - 1, shape, dtype=np.int32)
            else:
                out[name] = rng.standard_normal(shape).astype(dtype)
        out["host"] = rng.standard_normal(1234).astype(np.float32)
        return out

    def mesh_state(seed):
        host = host_state(seed)
        out = {k: jax.device_put(v, NamedSharding(mesh, SPECS[k][2])) for k, v in host.items() if k in SPECS}
        out["host"] = host["host"]
        return out

    def targets(state):
        return {k: (v.sharding if isinstance(v, jax.Array) else NamedSharding(mesh, P()))
                for k, v in state.items()}

    def same_bits(a, b):
        return np.asarray(a).dtype == np.asarray(b).dtype and np.asarray(a).tobytes() == np.asarray(b).tobytes()

    def assert_on_mesh(out, state):
        """Every leaf has its target's sharding and each device's shard is
        bit for bit the saved one's on that device."""
        for name, want in targets(state).items():
            got = out[name]
            assert got.sharding == want, (name, got.sharding, want)
            ref = state[name]
            for s in got.addressable_shards:
                expect = np.asarray(ref)[s.index] if not isinstance(ref, jax.Array) else next(
                    np.asarray(r.data) for r in ref.addressable_shards if r.device == s.device)
                assert same_bits(s.data, expect), (name, s.device)

    def assert_whole(out, state):
        for name, ref in state.items():
            assert same_bits(out[name], ref), name

    def by_name(ck):
        return {info.path.strip("[]'"): info for info in ck.layout}

    def count_writes(ck):
        """Wrap every writer's ``write`` on the instance; returns the list
        of (handle, offset, bytes, version) of every call."""
        calls = []
        lock = threading.Lock()
        for handle in [ck.handle, *ck.shard_handles.values()]:
            def counted(buf, offset, _handle=handle, _write=handle.write):
                version = _write(buf, offset)
                with lock:
                    calls.append((_handle, offset, buf.nbytes, version))
                return version
            handle.write = counted
        return calls

    def serial_digests(ck, state):
        """The save's digests, computed plainly: each region's C-order bytes
        (the whole leaf, or the shard's slice of it) cut into pages, the
        last zero-padded, blake2b with 16-byte digests."""
        out = {}
        leaves = jax.tree.leaves(state)
        for info, leaf in zip(ck.layout, leaves):
            whole = np.asarray(leaf)
            for r in info.regions:
                raw = (whole if r.device is None else whole[r.slices]).tobytes()
                assert len(raw) == r.size
                for p in range(-(-len(raw) // PAGE)):
                    page = raw[p * PAGE : (p + 1) * PAGE].ljust(PAGE, b"\0")
                    out[r.offset // PAGE + p] = hashlib.blake2b(page, digest_size=16).digest()
        return out

    def case_round_trip_onto_the_mesh():
        state = mesh_state(1)
        ck = BlobCheckpointer(session(), state, page_size=PAGE)
        info = by_name(ck)
        assert {k: len(v.regions) for k, v in info.items()} == DISTINCT
        for name, v in info.items():
            assert all(r.offset % PAGE == 0 for r in v.regions)
            if DISTINCT[name] > 1:
                assert all(r.size % PAGE for r in v.regions)  # none a page multiple
        rec = ck.save(0, state)
        assert rec.shards == 4 and rec.dirty_pages == rec.total_pages > 0
        assert json.loads(ck.manifest())["checkpoints"][0]["shards"] == 4
        assert_on_mesh(ck.restore(0, shardings=targets(state)), state)

    def case_digests_match_a_serial_per_shard_reference():
        empty = np.empty

        def used_empty(*args, **kwargs):  # padding left unzeroed would show
            out = empty(*args, **kwargs)
            if out.dtype == np.uint8:
                out.fill(0xAB)
            return out

        np.empty = used_empty
        try:
            state = mesh_state(2)
            ck = BlobCheckpointer(session(), state, page_size=PAGE)
            rec = ck.save(0, state)
            ref = serial_digests(ck, state)
            assert ck._page_hash == ref
            assert rec.dirty_pages == rec.total_pages == len(ref)
            for info, leaf in zip(ck.layout, jax.tree.leaves(state)):
                for r in info.regions:
                    span = -(-r.size // PAGE) * PAGE
                    stored = ck.handle.read(r.offset, span, version=rec.version).data.tobytes()
                    want = np.asarray(leaf)[r.slices] if r.device is not None else np.asarray(leaf)
                    assert stored == want.tobytes().ljust(span, b"\0"), info.path
            # the host copy save_async takes (NumPy leaves, cut into the
            # same slices) hashes alike: nothing is dirty
            t = ck.save_async(1, state)
            t.join(timeout=60)
            assert not t.is_alive()
            rec = ck.checkpoints[-1]
            assert (rec.step, rec.dirty_pages, rec.total_pages) == (1, 0, len(ref))
            # a NumPy state with every leaf changed: the reference again
            moved = host_state(3)
            rec = ck.save(2, moved)
            assert ck._page_hash == serial_digests(ck, moved)
            assert rec.dirty_pages == rec.total_pages
            assert_whole(ck.restore(2), moved)
            assert_on_mesh(ck.restore(0, shardings=targets(state)), state)
        finally:
            np.empty = empty

    def case_one_changed_shard_writes_only_its_pages():
        state = mesh_state(4)
        ck = BlobCheckpointer(session(), state, page_size=PAGE)
        ck.save(0, state)
        calls = count_writes(ck)
        rows = state["rows"]
        region = by_name(ck)["rows"].regions[2]
        arrays = []
        for s in rows.addressable_shards:
            data = s.data
            if s.device.id == region.device:
                data = data.at[0, 0].add(1.0)
            arrays.append(data)
        changed = dict(state, rows=jax.make_array_from_single_device_arrays(rows.shape, rows.sharding, arrays))
        rec = ck.save(1, changed)
        assert [(h, off, n) for h, off, n, _ in calls] == [(ck.shard_handles[region.device], region.offset, PAGE)]
        assert rec.dirty_pages == 1 and rec.version == calls[0][3]
        assert_on_mesh(ck.restore(0, shardings=targets(state)), state)
        assert_on_mesh(ck.restore(1, shardings=targets(state)), changed)

    def check_late_writer(ck, state, first, rec, calls):
        """The record's version is the newest of the save's writes, and a
        restore there holds every shard of the save; the next save's changes
        are in none of it."""
        assert rec.version == max(v for *_, v in calls)
        assert ck.handle.latest_published() >= rec.version
        assert_on_mesh(ck.restore(first, shardings=targets(state)), state)
        later = mesh_state(9)
        ck.save(first + 1, later)
        assert_on_mesh(ck.restore(first, shardings=targets(state)), state)
        assert_on_mesh(ck.restore(first + 1, shardings=targets(state)), later)

    def case_a_writer_that_finishes_last():
        """The first device's writer writes only once every other writer
        has returned, so its versions are the newest of the save."""
        state = mesh_state(5)
        ck = BlobCheckpointer(session(), state, page_size=PAGE)
        slow = ck.shard_handles[ck.layout[0].regions[0].device]
        others_done = threading.Event()
        done = []
        save_shard = ck._save_shard

        def counted(handle, *args):
            out = save_shard(handle, *args)
            if handle is not slow:
                done.append(handle)
                if len(done) == len(ck.shard_handles):  # the three others and the whole leaves
                    others_done.set()
            return out

        ck._save_shard = counted
        calls = count_writes(ck)
        write = slow.write

        def late(buf, offset):
            assert others_done.wait(timeout=30)
            return write(buf, offset)

        slow.write = late
        rec = ck.save(0, state)
        last = [v for h, *_, v in calls if h is slow]
        assert min(last) > max(v for h, *_, v in calls if h is not slow)
        check_late_writer(ck, state, 0, rec, calls)

    def case_a_page_put_that_completes_late():
        """One page put of the save is held back: the versions after its
        write complete first, and none of them publishes before it."""
        state = mesh_state(6)
        ck = BlobCheckpointer(session(), state, page_size=PAGE)
        calls = count_writes(ck)
        vm = ck.session.cluster.version_manager
        held, seen = threading.Event(), {}

        def gate(op, provider_id):
            if op == "put_pages" and not held.is_set():
                held.set()
                time.sleep(0.3)
                seen["published"] = vm.latest_published(ck.blob_id)
                seen["assigned"] = vm.assigned_versions(ck.blob_id)

        providers = ck.session.cluster.provider_manager.providers()
        for p in providers:
            p.fault_gate = gate
        try:
            rec = ck.save(0, state)
        finally:
            for p in providers:
                p.fault_gate = None
        assert held.is_set() and seen["published"] < rec.version
        check_late_writer(ck, state, 0, rec, calls)

    def case_a_single_device_state_keeps_its_layout_and_writes():
        rng = np.random.default_rng(7)
        dev = devices[2]
        state = {
            "a": jax.device_put(rng.standard_normal((37, 29)).astype(np.float32), SingleDeviceSharding(dev)),
            "b": jax.device_put(rng.standard_normal(70001).astype(np.float32), SingleDeviceSharding(dev)),
            "n": rng.standard_normal(1001).astype(np.float32),
            "r": jax.device_put(np.arange(5, dtype=np.int32), NamedSharding(mesh, P())),
            "s": jax.device_put(np.float32(3.5), SingleDeviceSharding(dev)),
        }
        ck = BlobCheckpointer(session(), state, page_size=PAGE)
        off, want = 0, []
        for info, leaf in zip(ck.layout, jax.tree.leaves(state)):
            span = -(-leaf.nbytes // PAGE) * PAGE
            assert (info.offset, info.size, len(info.regions)) == (off, leaf.nbytes, 1)
            assert (info.regions[0].offset, info.regions[0].device) == (off, None)
            want.append((ck.handle, off, span))
            off += span
        assert ck.shard_handles == {}
        calls = count_writes(ck)
        rec = ck.save(0, state)
        assert [(h, o, n) for h, o, n, _ in calls] == want
        assert rec.shards == 1 and rec.version == calls[-1][3]
        out = ck.restore(0, shardings=SingleDeviceSharding(dev))
        assert all(x.sharding == SingleDeviceSharding(dev) for x in jax.tree.leaves(out))
        assert_whole(out, state)

    def case_restore_onto_one_device_the_host_or_another_mesh():
        state = mesh_state(8)
        ck = BlobCheckpointer(session(), state, page_size=PAGE)
        ck.save(0, state)
        one = SingleDeviceSharding(devices[1])
        out = ck.restore(0, shardings=one)
        assert all(x.sharding == one for x in jax.tree.leaves(out))
        assert_whole(out, state)
        out = ck.restore(0)
        assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(out))
        assert_whole(out, state)
        other = Mesh(np.array(devices).reshape(2, 2).T, ("x", "y"))
        moved = {k: NamedSharding(other, t.spec) for k, t in targets(state).items()}
        out = ck.restore(0, shardings=moved)
        assert all(out[k].sharding == moved[k] for k in moved)
        assert_whole(out, state)

    def case_save_and_restore_keep_to_each_shard():
        """No host array of a whole sharded leaf is made in the save, each
        device's shards are written by a writer of its own, and a restore
        onto the mesh reads each device's shards through its own handle and
        puts each straight on its device."""
        state = mesh_state(10)
        ck = BlobCheckpointer(session(), state, page_size=PAGE)
        info = by_name(ck)
        whole = {v.shape for k, v in info.items() if DISTINCT[k] > 1}
        to_host = []
        array = ArrayImpl.__array__

        def recorded(self, *args, **kwargs):
            to_host.append(self.shape)
            return array(self, *args, **kwargs)

        calls = count_writes(ck)
        ArrayImpl.__array__ = recorded
        try:
            ck.save(0, state)
        finally:
            ArrayImpl.__array__ = array
        assert to_host and not whole & set(to_host), to_host
        sessions = {h.session for h in ck.shard_handles.values()}
        assert len(sessions) == 4 and ck.session not in sessions
        for device, handle in ck.shard_handles.items():
            mine = sorted(r.offset for v in ck.layout for r in v.regions if r.device == device)
            assert sorted(o for h, o, *_ in calls if h is handle) == mine
        assert sorted(o for h, o, *_ in calls if h is ck.handle) == sorted(
            v.offset for v in ck.layout if not v.sharded)

        reads, puts = [], []
        for handle in [ck.handle, *ck.shard_handles.values()]:
            def counted(offset, size, version=None, _handle=handle, _read=handle.read):
                reads.append((_handle, offset))
                return _read(offset, size, version=version)
            handle.read = counted
        device_put = jax.device_put

        def put(x, device=None, *args, **kwargs):
            puts.append((x, device))
            return device_put(x, device, *args, **kwargs)

        jax.device_put = put
        try:
            out = ck.restore(0, shardings=targets(state))
        finally:
            jax.device_put = device_put
        assert_on_mesh(out, state)
        for device, handle in ck.shard_handles.items():
            mine = sorted(r.offset for v in ck.layout for r in v.regions if r.device == device)
            assert sorted(o for h, o in reads if h is handle) == mine
        assert sorted(o for h, o in reads if h is ck.handle) == sorted(
            v.offset for v in ck.layout if not v.sharded)
        shard_puts = [(x, d) for x, d in puts if isinstance(d, jax.Device)]
        assert len(shard_puts) == sum(4 for k in DISTINCT if DISTINCT[k] > 1)
        for x, d in shard_puts:
            assert isinstance(x, np.ndarray) and x.shape not in whole
        assert all(not isinstance(d, jax.Device) for x, d in puts if not isinstance(x, np.ndarray)
                   or x.shape in whole)

    return {name[len("case_"):]: fn for name, fn in locals().items() if name.startswith("case_")}


def _child() -> None:
    results = {}
    for name, case in _child_cases().items():
        try:
            case()
            results[name] = "ok"
        except Exception:
            results[name] = traceback.format_exc()
    print(json.dumps(results))


# ------------------------------ in the tests ------------------------------
CASES = [
    "round_trip_onto_the_mesh",
    "digests_match_a_serial_per_shard_reference",
    "one_changed_shard_writes_only_its_pages",
    "a_writer_that_finishes_last",
    "a_page_put_that_completes_late",
    "a_single_device_state_keeps_its_layout_and_writes",
    "restore_onto_one_device_the_host_or_another_mesh",
    "save_and_restore_keep_to_each_shard",
]


@pytest.fixture(scope="module")
def outcomes():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(filter(None, [str(ROOT / "src"), os.environ.get("PYTHONPATH")])))
    proc = subprocess.run([sys.executable, __file__], env=env, capture_output=True, text=True,
                          timeout=300, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", CASES)
def test_sharded_checkpoint(outcomes, case):
    assert outcomes.get(case) == "ok", outcomes.get(case, f"case {case} did not run")


def test_every_case_is_listed(outcomes):
    assert sorted(outcomes) == sorted(CASES)


if __name__ == "__main__":
    _child()
