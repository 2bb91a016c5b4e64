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
import threading
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
        """Write dirty pages of ``state``; returns the checkpoint record."""
        with self._lock:
            leaves = _leaf_paths(state)
            assert len(leaves) == len(self.layout), "state structure changed"
            dirty_runs: List[Tuple[int, bytes]] = []  # (page_index, page_bytes...)
            dirty = 0
            total_pages_touched = 0
            ps = self.page_size

            run_start: Optional[int] = None
            run_chunks: List[bytes] = []

            def flush_run():
                nonlocal run_start, run_chunks
                if run_start is not None:
                    dirty_runs.append((run_start, b"".join(run_chunks)))
                run_start, run_chunks = None, []

            for info, (path, leaf) in zip(self.layout, leaves):
                arr = np.ascontiguousarray(jax.device_get(leaf))
                raw = arr.tobytes()
                n_pages = -(-len(raw) // ps)
                total_pages_touched += n_pages
                first_page = info.offset // ps
                for p in range(n_pages):
                    chunk = raw[p * ps : (p + 1) * ps]
                    if len(chunk) < ps:
                        chunk = chunk + b"\0" * (ps - len(chunk))
                    h = hashlib.blake2b(chunk, digest_size=16).digest()
                    page_idx = first_page + p
                    if self._page_hash.get(page_idx) == h:
                        flush_run()
                        continue
                    self._page_hash[page_idx] = h
                    dirty += 1
                    if run_start is None:
                        run_start = page_idx
                    elif run_start + len(run_chunks) != page_idx:
                        flush_run()
                        run_start = page_idx
                    run_chunks.append(chunk)
                flush_run()

            version = self.handle.latest_published()
            for page_idx, data in dirty_runs:
                buf = np.frombuffer(data, dtype=np.uint8)
                version = self.handle.write(buf, page_idx * ps)

            rec = CheckpointRecord(step, version, dirty, total_pages_touched)
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
