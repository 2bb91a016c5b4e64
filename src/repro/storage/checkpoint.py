"""Incremental, versioned, crash-consistent checkpointing on the blob store.

The training state (params + optimizer) is serialized into ONE logical blob
with a page-aligned layout of regions. Each checkpoint WRITEs only the
*dirty* pages (content hash changed since the previous version) — the
paper's patching — so consecutive checkpoints share all unchanged pages
(COW), old checkpoints stay readable while the next one is being written
(read/write concurrency), and a checkpoint becomes visible only when its
last write publishes (atomicity: a crash mid-save leaves the previous
version intact).

The template decides the layout. A leaf with one distinct shard (a NumPy
array, a single-device or replicated ``jax.Array``) is one region holding
its C-order bytes. A ``jax.Array`` split into several distinct shards gets a
region per shard, as ByteCheckpoint's ranks each keep their own: each
region holds that shard's C-order bytes and records the device it came
from and its slice of the leaf. A save hashes and writes each device's
shards from that device's own host copy, through a session of its own, all
devices at once on one blob; the version manager publishes versions in
order, so the checkpoint is visible once the last of them publishes. A
restore onto the devices the shards came from reads each device's regions
in parallel and puts them straight on their device; any other target
(the host, one device, another mesh) assembles whole leaves on the host
first, so a checkpoint can be resumed on a different mesh.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.analysis.lockwatch import make_lock
from repro.core.cluster import BlobHandle, Session

#: a shard's part of its leaf: (start, stop) along each axis
Index = Tuple[Tuple[int, int], ...]


@dataclasses.dataclass
class Region:
    """A page-aligned span of the blob holding one leaf's bytes, or one
    shard's."""

    offset: int  # byte offset in the blob (page aligned)
    size: int
    shape: Tuple[int, ...]
    device: Optional[int] = None  # id of the device a shard was saved from
    index: Index = ()  # the shard's part of the leaf

    @property
    def slices(self) -> Tuple[slice, ...]:
        return tuple(slice(a, b) for a, b in self.index)


@dataclasses.dataclass
class LeafInfo:
    path: str
    offset: int  # byte offset in the blob (page aligned) of its first region
    size: int  # bytes of the whole leaf
    dtype: str
    shape: Tuple[int, ...]
    #: one whole-leaf region, or one region a distinct shard in index order
    regions: List[Region] = dataclasses.field(default_factory=list)

    @property
    def sharded(self) -> bool:
        return self.regions[0].device is not None


@dataclasses.dataclass
class CheckpointRecord:
    step: int
    version: int  # blob version at which this checkpoint is complete
    dirty_pages: int
    total_pages: int
    workers: int = 1  # threads that hashed the pages
    host_wait_s: float = 0.0  # longest wait of one writer on device-to-host copies
    shards: int = 1  # devices whose shards were saved, each by its own writer


#: bytes of a leaf one save task hashes: 1-MiB pages go 8 to a task, 4-KiB
#: pages 2048
_TASK_BYTES = 8 << 20


def _copy_c_order(dst: np.ndarray, src: np.ndarray, start: int, stop: int) -> None:
    """Copy elements ``start:stop`` of ``src``'s C-order flattening to the
    same places of ``dst``, a 1-d array of ``src.size`` elements: whole
    slabs along the first axis in one copy each, a slab cut by either end
    recursively."""
    if src.ndim <= 1:
        dst[start:stop] = src.reshape(-1)[start:stop]
        return
    row = src[0].size
    lo, hi = -(-start // row), stop // row  # the slabs wholly inside
    if lo > hi:  # inside one slab
        r = start // row
        _copy_c_order(dst[r * row : (r + 1) * row], src[r], start - r * row, stop - r * row)
        return
    if start < lo * row:
        _copy_c_order(dst[(lo - 1) * row : lo * row], src[lo - 1], start - (lo - 1) * row, row)
    if lo < hi:
        dst[lo * row : hi * row].reshape(src[lo:hi].shape)[...] = src[lo:hi]
    if stop > hi * row:
        _copy_c_order(dst[hi * row : (hi + 1) * row], src[hi], 0, stop - hi * row)


def _hash_pages(
    arr: np.ndarray, stage: np.ndarray, offset: int, ps: int, lo: int, hi: int,
    old: List[Optional[bytes]],
) -> List[Optional[bytes]]:
    """Digest pages ``lo:hi`` of one leaf's C-order bytes (its last page
    zero-padded): the digest of each page whose digest is not its ``old``
    one, and ``None`` for each page that is clean. ``stage`` at the leaf's
    ``offset`` ends up holding every dirty page, zero-padded.

    A C-contiguous leaf is read in place and only those pages are copied.
    Any other leaf (a device array can come back in its device layout) has
    the pages copied into ``stage`` in C order first, and read there."""
    own = stage[offset : offset + arr.nbytes]
    if arr.flags.c_contiguous:
        flat = arr.reshape(-1).view(np.uint8)
    else:
        isz = arr.itemsize
        _copy_c_order(own.view(arr.dtype), arr, lo * ps // isz, min(-(-hi * ps // isz), arr.size))
        flat = own
    digests: List[Optional[bytes]] = []
    for p, prev in zip(range(lo, hi), old):
        page = flat[p * ps : (p + 1) * ps]
        h = hashlib.blake2b(page, digest_size=16)
        if page.size < ps:
            h.update(bytes(ps - page.size))
        digest = h.digest()
        if digest == prev:
            digests.append(None)
            continue
        dst = stage[offset + p * ps : offset + (p + 1) * ps]
        if flat is not own:
            dst[: page.size] = page
        dst[page.size :] = 0
        digests.append(digest)
    return digests


def _leaf_paths(tree) -> List[Tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(k), v) for k, v in flat]


def _index(index: Tuple[slice, ...], shape: Tuple[int, ...]) -> Index:
    return tuple(s.indices(n)[:2] for s, n in zip(index, shape))


def _distinct_shards(leaf: Any) -> List[Tuple[int, Index]]:
    """``(device id, index)`` of each distinct shard of a fully addressable
    ``jax.Array`` cut into more than one, in index order; ``[]`` for any
    other leaf (which is saved whole)."""
    if not isinstance(leaf, jax.Array) or not leaf.is_fully_addressable:
        return []
    shards = sorted(
        (_index(s.index, leaf.shape), s.device.id)
        for s in leaf.addressable_shards
        if s.replica_id == 0
    )
    return [(device, index) for index, device in shards] if len(shards) > 1 else []


def _nbytes(leaf: Any) -> int:
    return leaf.nbytes if hasattr(leaf, "nbytes") else np.asarray(leaf).nbytes


class BlobCheckpointer:
    def __init__(
        self,
        session: Session,
        template: Any,
        page_size: int = 1 << 20,
        keep_last: int = 3,
    ) -> None:
        self.session = session
        self.page_size = page_size
        self.keep_last = keep_last
        self._lock = make_lock("BlobCheckpointer._lock")

        self.layout: List[LeafInfo] = []
        off = 0
        for path, leaf in _leaf_paths(template):
            itemsize = np.dtype(leaf.dtype).itemsize
            shape = tuple(leaf.shape)
            regions = []
            for device, index in _distinct_shards(leaf) or [(None, ())]:
                part = tuple(b - a for a, b in index) if device is not None else shape
                size = int(np.prod(part)) * itemsize
                regions.append(Region(off, size, part, device, index))
                off += -(-size // page_size) * page_size  # page-align every region
            size = int(np.prod(shape)) * itemsize
            self.layout.append(LeafInfo(path, regions[0].offset, size, str(leaf.dtype), shape, regions))
        total = max(off, page_size)
        # blob sizes are powers of two (paper §II)
        self.blob_bytes = 1 << (total - 1).bit_length()
        self.handle = session.create(self.blob_bytes, page_size)
        self.blob_id = self.handle.blob_id
        self.n_pages = self.blob_bytes // page_size
        # the writers of a save, each with its (leaf, region) pairs in blob
        # order: this handle for whole leaves, and for each device whose
        # shards are saved a session and handle of its own on the same blob
        groups: Dict[Optional[int], List[Tuple[int, int]]] = {}
        for i, info in enumerate(self.layout):
            for j, region in enumerate(info.regions):
                groups.setdefault(region.device, []).append((i, j))
        self._groups = list(groups.items())
        cache = session.cache.capacity_bytes if session.cache is not None else 0
        self.shard_handles: Dict[int, BlobHandle] = {
            device: session.cluster.session(cache_bytes=cache).open(self.blob_id)
            for device in groups
            if device is not None
        }
        self._page_hash: Dict[int, bytes] = {}
        self.checkpoints: List[CheckpointRecord] = []
        self._treedef = jax.tree.structure(template)

    def _writer(self, device: Optional[int]) -> BlobHandle:
        return self.handle if device is None else self.shard_handles[device]

    # -- save -------------------------------------------------------------------------
    def save(self, step: int, state: Any) -> CheckpointRecord:
        """Write dirty pages of ``state``; returns the checkpoint record.

        Every region's copy to the host starts first. Then writer by writer
        (one for the whole leaves, one for each device's shards), as each of
        its regions lands, a pool of threads hashes the region's pages and
        fills the writer's staging buffer with the dirty ones. Once a
        writer's pages are hashed it writes its runs of dirty pages (a run
        ends at a clean page and at a region's end), one ``write`` each,
        while the next writers' pages are still being hashed.
        """
        with self._lock:
            leaves = [leaf for _, leaf in _leaf_paths(state)]
            if len(leaves) != len(self.layout):
                raise ValueError("state structure changed")
            sources = [self._sources(info, leaf) for info, leaf in zip(self.layout, leaves)]
            for src in (src for srcs in sources for src in srcs):
                if isinstance(src, jax.Array):
                    src.copy_to_host_async()
            ps = self.page_size
            per_task = max(1, _TASK_BYTES // ps)
            n_tasks = sum(-(-r.size // (per_task * ps)) for info in self.layout for r in info.regions)
            workers = max(1, min(n_tasks, len(os.sched_getaffinity(0))))
            pool = ThreadPoolExecutor(workers)  # one for the whole save
            writers = ThreadPoolExecutor(len(self._groups))
            saved: List[Future] = []
            waits: List[float] = []
            try:
                for device, members in self._groups:
                    # the writer's one staging copy: its dirty pages land
                    # here (every page of a region that is not C-contiguous,
                    # for hashing in C order)
                    regions = [self.layout[i].regions[j] for i, j in members]
                    stage = np.empty(sum(-(-r.size // ps) for r in regions) * ps, np.uint8)
                    pieces: List[Tuple[int, int, List[Future]]] = []  # (page, stage page, tasks)
                    wait, at = 0.0, 0
                    for (i, j), region in zip(members, regions):
                        t0 = time.perf_counter()
                        arr = np.asarray(sources[i][j])
                        wait += time.perf_counter() - t0
                        first = region.offset // ps
                        n_pages = -(-region.size // ps)
                        tasks = []
                        for lo in range(0, n_pages, per_task):
                            hi = min(lo + per_task, n_pages)
                            old = [self._page_hash.get(first + p) for p in range(lo, hi)]
                            tasks.append(pool.submit(_hash_pages, arr, stage, at * ps, ps, lo, hi, old))
                        pieces.append((first, at, tasks))
                        at += n_pages
                    waits.append(wait)
                    saved.append(writers.submit(self._save_shard, self._writer(device), stage, pieces))
                done = [f.result() for f in saved]
            finally:
                pool.shutdown(cancel_futures=True)
                writers.shutdown()

            fresh: Dict[int, bytes] = {}
            total_pages = 0
            versions = []
            for version, digests, n_pages in done:
                fresh.update(digests)
                total_pages += n_pages
                if version is not None:
                    versions.append(version)
            self._page_hash.update(fresh)
            # versions publish in order: once the newest of the save's
            # writes is published, every one of them is
            version = max(versions) if versions else self.handle.latest_published()
            rec = CheckpointRecord(step, version, len(fresh), total_pages, workers,
                                   max(waits), max(1, len(self.shard_handles)))
            self.checkpoints.append(rec)
            self._gc()
            return rec

    def _sources(self, info: LeafInfo, leaf: Any) -> List[Any]:
        """What each region of a leaf is saved from: the leaf itself when it
        is saved whole; for shards, the leaf's own shards when it is a
        ``jax.Array`` sharded as the template was, else the shards' slices of
        the leaf on the host."""
        if tuple(np.shape(leaf)) != info.shape if info.sharded else _nbytes(leaf) != info.size:
            raise ValueError(f"leaf {info.path} changed size")
        if not info.sharded:
            return [leaf]
        if isinstance(leaf, jax.Array):
            own = {(s.device.id, _index(s.index, leaf.shape)): s.data for s in leaf.addressable_shards}
            shards = [own.get((r.device, r.index)) for r in info.regions]
            if all(s is not None for s in shards):
                return shards
        host = np.asarray(leaf)
        return [host[r.slices] for r in info.regions]

    def _save_shard(
        self, handle: BlobHandle, stage: np.ndarray, pieces: List[Tuple[int, int, List[Future]]]
    ) -> Tuple[Optional[int], Dict[int, bytes], int]:
        """One writer's part of a save: gather its regions' digests in page
        order, then write each run of dirty pages from its staging buffer
        through its own handle. Returns the last version written (``None``
        if nothing was), the fresh digests and the number of pages hashed."""
        ps = self.page_size
        fresh: Dict[int, bytes] = {}
        runs: List[List[int]] = []  # [page, stage page, pages] of each dirty run
        total = 0
        for first, at, tasks in pieces:
            run: Optional[List[int]] = None
            n = 0
            for task in tasks:
                for digest in task.result():
                    if digest is None:
                        run = None
                    else:
                        fresh[first + n] = digest
                        if run is None:
                            run = [first + n, at + n, 0]
                            runs.append(run)
                        run[2] += 1
                    n += 1
            total += n
        # the store keeps what it is given until the pages are superseded:
        # read-only views of the staging buffer when the runs tile it (every
        # page dirty), else a copy of each run, so that it keeps only the
        # dirty bytes
        stage.flags.writeable = False
        tiled = len(fresh) == total
        version = None
        for page, at, n in runs:
            buf = stage[at * ps : (at + n) * ps]
            version = handle.write(buf if tiled else buf.copy(), page * ps)
        return version, fresh, total

    def save_async(self, step: int, state: Any) -> threading.Thread:
        """Snapshot to host then write in a background thread (training
        proceeds concurrently — the paper's read/write concurrency)."""
        host_state = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), state)
        t = threading.Thread(target=self.save, args=(step, host_state), daemon=True)
        t.start()
        return t

    # -- restore ----------------------------------------------------------------------
    def restore(self, step: Optional[int] = None, shardings: Any = None) -> Any:
        """Rebuild the state pytree from the blob (any retained step).

        ``shardings``: optional sharding for every leaf, or a pytree of
        them, to reshard onto a (possibly different) mesh — elastic restart.
        A sharded leaf whose target puts every saved shard back on the
        device it came from is read shard by shard, each device's shards
        through that device's own handle and all devices at once, and each
        shard is put straight on its devices. Every other leaf is assembled
        on the host and placed with ``jax.device_put``.
        """
        with self._lock:
            if not self.checkpoints:
                raise RuntimeError("no checkpoints saved")
            if step is None:
                rec = self.checkpoints[-1]
            else:
                rec = next(c for c in self.checkpoints if c.step == step)
        targets = self._targets(shardings)
        direct = {}
        for i, (info, target) in enumerate(zip(self.layout, targets)):
            holders = self._placement(info, target)
            if holders is not None:
                direct[i] = holders
        if not direct:
            leaves = [self._read_leaf(info, rec.version) for info in self.layout]
            state = jax.tree.unflatten(self._treedef, leaves)
            if shardings is not None:
                state = jax.device_put(state, shardings)
            return state

        jobs: Dict[int, List[Tuple[Region, str, List[jax.Device]]]] = {}
        owner: Dict[int, List[int]] = {}  # device -> the leaf of each of its jobs
        for i, holders in direct.items():
            info = self.layout[i]
            for region, devices in zip(info.regions, holders):
                jobs.setdefault(region.device, []).append((region, info.dtype, devices))
                owner.setdefault(region.device, []).append(i)
        readers = ThreadPoolExecutor(len(jobs))
        try:
            reads = {d: readers.submit(self._restore_shard, self.shard_handles[d], rec.version, js)
                     for d, js in jobs.items()}
            leaves = [None if i in direct else self._read_leaf(info, rec.version)
                      for i, info in enumerate(self.layout)]
            placed: Dict[int, List[jax.Array]] = {i: [] for i in direct}
            for d, f in reads.items():
                for i, arrays in zip(owner[d], f.result()):
                    placed[i].extend(arrays)
        finally:
            readers.shutdown()
        for i, arrays in placed.items():
            leaves[i] = jax.make_array_from_single_device_arrays(
                self.layout[i].shape, targets[i], arrays)
        rest = [i for i in range(len(leaves)) if i not in direct]
        for i, leaf in zip(rest, jax.device_put([leaves[i] for i in rest], [targets[i] for i in rest])):
            leaves[i] = leaf
        return jax.tree.unflatten(self._treedef, leaves)

    def _read_leaf(self, info: LeafInfo, version: int) -> np.ndarray:
        """One leaf as a host array, read through this handle; a sharded
        leaf is assembled from its shards."""
        if not info.sharded:
            res = self.handle.read(info.offset, info.size, version=version)
            return np.frombuffer(res.data.tobytes(), dtype=info.dtype).reshape(info.shape)
        out = np.empty(info.shape, info.dtype)
        for r in info.regions:
            res = self.handle.read(r.offset, r.size, version=version)
            out[r.slices] = res.data.view(info.dtype).reshape(r.shape)
        return out

    def _restore_shard(
        self, handle: BlobHandle, version: int, jobs: List[Tuple[Region, str, List[jax.Device]]]
    ) -> List[List[jax.Array]]:
        """One device's part of a restore: read each of its shards through
        its own handle and put it on every device that holds that shard
        (the one it came from, and any replicas of it)."""
        out = []
        for region, dtype, devices in jobs:
            data = handle.read(region.offset, region.size, version=version).data
            shard = data.view(dtype).reshape(region.shape)
            out.append([jax.device_put(shard, d) for d in devices])
        return out

    def _targets(self, shardings: Any) -> List[Any]:
        """The target of each leaf, where ``shardings`` names one per leaf;
        otherwise (none, or a pytree prefix) ``None`` for each."""
        n = len(self.layout)
        if shardings is None or not any(info.sharded for info in self.layout):
            return [None] * n
        if isinstance(shardings, jax.sharding.Sharding):
            return [shardings] * n
        if jax.tree.structure(shardings) != self._treedef:
            return [None] * n
        return jax.tree.leaves(shardings)

    @staticmethod
    def _placement(info: LeafInfo, target: Any) -> Optional[List[List[jax.Device]]]:
        """For each shard of a sharded leaf, the devices of ``target`` that
        hold exactly that shard, when ``target`` puts every saved shard back
        on the device it came from; ``None`` otherwise."""
        if (not info.sharded or not isinstance(target, jax.sharding.Sharding)
                or not target.is_fully_addressable):
            return None
        holders: Dict[Index, List[jax.Device]] = {r.index: [] for r in info.regions}
        for device, index in target.addressable_devices_indices_map(info.shape).items():
            got = holders.get(_index(index, info.shape))
            if got is None:
                return None
            got.append(device)
        if any(r.device not in {d.id for d in holders[r.index]} for r in info.regions):
            return None
        return [holders[r.index] for r in info.regions]

    # -- retention ----------------------------------------------------------------------
    def _gc(self) -> None:
        if len(self.checkpoints) <= self.keep_last:
            return
        keep = self.checkpoints[-self.keep_last :]
        self.session.cluster.gc(self.blob_id, [c.version for c in keep])
        self.checkpoints = keep

    def manifest(self) -> str:
        return json.dumps(
            {
                "blob_id": self.blob_id,
                "page_size": self.page_size,
                "checkpoints": [dataclasses.asdict(c) for c in self.checkpoints],
            }
        )
