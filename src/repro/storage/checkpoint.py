"""Incremental, versioned, crash-consistent checkpointing on the blob store.

The training state (params + optimizer) is serialized into ONE logical blob
with a page-aligned layout. Each checkpoint WRITEs only the *dirty* pages
(content hash changed since the previous version) — the paper's patching —
so consecutive checkpoints share all unchanged pages (COW), old checkpoints
stay readable while the next one is being written (read/write concurrency),
and a checkpoint becomes visible only when its last write publishes
(atomicity: a crash mid-save leaves the previous version intact).

Restore can target any retained step and reshard to a different mesh — the
blob is mesh-agnostic bytes; elasticity comes for free.
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
from repro.core.cluster import Session


@dataclasses.dataclass
class LeafInfo:
    path: str
    offset: int  # byte offset in the blob (page aligned)
    size: int
    dtype: str
    shape: Tuple[int, ...]


@dataclasses.dataclass
class CheckpointRecord:
    step: int
    version: int  # blob version at which this checkpoint is complete
    dirty_pages: int
    total_pages: int
    workers: int = 1  # threads that hashed the pages
    host_wait_s: float = 0.0  # seconds blocked on device-to-host copies


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

        leaves = _leaf_paths(template)
        self.layout: List[LeafInfo] = []
        off = 0
        for path, leaf in leaves:
            size = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize if leaf.shape else np.dtype(leaf.dtype).itemsize
            self.layout.append(LeafInfo(path, off, size, str(leaf.dtype), tuple(leaf.shape)))
            off += -(-size // page_size) * page_size  # page-align every leaf
        self._span = off
        total = max(off, page_size)
        # blob sizes are powers of two (paper §II)
        self.blob_bytes = 1 << (total - 1).bit_length()
        self.handle = session.create(self.blob_bytes, page_size)
        self.blob_id = self.handle.blob_id
        self.n_pages = self.blob_bytes // page_size
        self._page_hash: Dict[int, bytes] = {}
        self.checkpoints: List[CheckpointRecord] = []
        self._treedef = jax.tree.structure(template)

    # -- save -------------------------------------------------------------------------
    def save(self, step: int, state: Any) -> CheckpointRecord:
        """Write dirty pages of ``state``; returns the checkpoint record.

        Every device leaf's copy to the host starts first; leaf by leaf, as
        each lands, a pool of threads hashes its pages and fills one staging
        buffer with the dirty ones, while later leaves are still in flight.
        Runs of dirty pages (a run ends at a clean page and at a leaf's end)
        are written from that buffer, one ``write`` each.
        """
        with self._lock:
            leaves = [leaf for _, leaf in _leaf_paths(state)]
            if len(leaves) != len(self.layout):
                raise ValueError("state structure changed")
            for leaf in leaves:
                if isinstance(leaf, jax.Array):
                    leaf.copy_to_host_async()
            ps = self.page_size
            per_task = max(1, _TASK_BYTES // ps)
            n_tasks = sum(-(-info.size // (per_task * ps)) for info in self.layout)
            workers = max(1, min(n_tasks, len(os.sched_getaffinity(0))))
            # the one staging copy: dirty pages land here (every page of a
            # leaf that is not C-contiguous, for hashing in C order)
            stage = np.empty(self._span, np.uint8)
            pool = ThreadPoolExecutor(workers)
            hashed: List[Tuple[int, List[Future]]] = []  # (first page, tasks) a leaf
            host_wait = 0.0
            try:
                for info, leaf in zip(self.layout, leaves):
                    t0 = time.perf_counter()
                    arr = np.asarray(leaf)
                    host_wait += time.perf_counter() - t0
                    if arr.nbytes != info.size:
                        raise ValueError(f"leaf {info.path} changed size")
                    first = info.offset // ps
                    n_pages = -(-info.size // ps)
                    tasks = []
                    for lo in range(0, n_pages, per_task):
                        hi = min(lo + per_task, n_pages)
                        old = [self._page_hash.get(first + p) for p in range(lo, hi)]
                        tasks.append(pool.submit(_hash_pages, arr, stage, info.offset, ps, lo, hi, old))
                    hashed.append((first, tasks))

                # gather in page order on this thread
                fresh: Dict[int, bytes] = {}
                runs: List[List[int]] = []  # [first page, pages] of each dirty run
                total_pages = 0
                for first, tasks in hashed:
                    run: Optional[List[int]] = None
                    page = first
                    for task in tasks:
                        for digest in task.result():
                            if digest is None:
                                run = None
                            else:
                                fresh[page] = digest
                                if run is None:
                                    run = [page, 0]
                                    runs.append(run)
                                run[1] += 1
                            page += 1
                    total_pages += page - first
            finally:
                pool.shutdown(cancel_futures=True)

            # the store keeps what it is given until the pages are superseded:
            # read-only views of the staging buffer when the runs tile it (every
            # page dirty), else a copy of each run, so that it keeps only the
            # dirty bytes
            stage.flags.writeable = False
            tiled = len(fresh) == total_pages
            version = self.handle.latest_published()
            for page_idx, n in runs:
                buf = stage[page_idx * ps : (page_idx + n) * ps]
                version = self.handle.write(buf if tiled else buf.copy(), page_idx * ps)
            self._page_hash.update(fresh)

            rec = CheckpointRecord(step, version, len(fresh), total_pages, workers, host_wait)
            self.checkpoints.append(rec)
            self._gc()
            return rec

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
        """
        with self._lock:
            if not self.checkpoints:
                raise RuntimeError("no checkpoints saved")
            if step is None:
                rec = self.checkpoints[-1]
            else:
                rec = next(c for c in self.checkpoints if c.step == step)
        leaves = []
        for info in self.layout:
            res = self.handle.read(info.offset, info.size, version=rec.version)
            arr = np.frombuffer(res.data.tobytes(), dtype=info.dtype).reshape(info.shape)
            leaves.append(arr)
        state = jax.tree.unflatten(self._treedef, leaves)
        if shardings is not None:
            state = jax.device_put(state, shardings)
        return state

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
