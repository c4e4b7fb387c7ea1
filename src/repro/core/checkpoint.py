"""Transparent, consistent, content-deduplicated checkpointing (§4, §4.6).

The checkpoint of an N-worker job is ``S_G + N * S_pwCr`` (paper §7.2):

- ``S_G``  — device state.  Per-buffer content checksums dedup identical
  buffers ACROSS workers: data-parallel replicas share identical parameter
  and optimizer tensors, so the stored device bytes are independent of the
  DP degree (Table 4's key property).
- ``S_Cr`` — per-worker host program state (CRIU analogue).  In this JAX
  framework the host state is the structured loop state (step counter, data
  cursor, RNG, schedule state); chunk-level content addressing gives the
  paper's page-dedup across workers, and TEMPORAL dedup makes incremental
  snapshots an order of magnitude smaller than the first one.

Chunks are content-addressed (blake2b-128); a snapshot is a manifest of
chunk references.  The store can live in memory or on disk.
"""
from __future__ import annotations

import dataclasses
import io
import json
import os
import pickle
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.utils.hashing import chunk_checksums
from repro.utils.profiler import Profiler

CHUNK = 1 << 20     # 1 MiB content chunks (page-dedup granularity)


def _leaf_bytes(leaf) -> bytes:
    arr = np.asarray(leaf)
    buf = io.BytesIO()
    np.save(buf, arr, allow_pickle=False)
    return buf.getvalue()


def _leaf_from_bytes(b: bytes):
    return np.load(io.BytesIO(b), allow_pickle=False)


@dataclasses.dataclass
class SnapshotStats:
    step: int
    device_logical_bytes: int      # sum over all workers (no dedup)
    device_stored_bytes: int       # unique bytes actually stored (S_G)
    host_logical_bytes: int        # sum of per-worker host dumps
    host_stored_bytes: int         # unique new chunks stored this snapshot
    n_workers: int


class CheckpointStore:
    """Content-addressed chunk store + snapshot manifests.

    Spans on ``profiler``: ``ckpt.put`` (a whole snapshot) holding, per
    device leaf of each worker, ``ckpt.serialize`` and ``ckpt.hash``
    (chunking, blake2b, dedup lookup, insertion); counters ``ckpt.bytes``
    and ``ckpt.bytes_new`` (device bytes serialized, and those new to the
    store).  ``restore.get`` (a whole restore) holding one
    ``restore.read`` per worker's device copy rebuilt."""

    def __init__(self, root: Optional[str] = None,
                 profiler: Optional[Profiler] = None):
        self.root = root
        self.prof = profiler if profiler is not None else Profiler()
        self.chunks: Dict[str, bytes] = {}
        self.manifests: Dict[str, List[Dict]] = {}     # job -> snapshots
        if root:
            os.makedirs(os.path.join(root, "chunks"), exist_ok=True)

    # ---------------------------------------------------------------- chunks
    def _put_chunk(self, data: bytes) -> Tuple[str, bool]:
        cs = chunk_checksums(data, len(data) or 1)[0] if len(data) <= CHUNK \
            else None
        if cs is None:
            raise ValueError("chunk too large")
        new = cs not in self.chunks
        if new:
            self.chunks[cs] = data
            if self.root:
                with open(os.path.join(self.root, "chunks", cs), "wb") as f:
                    f.write(data)
        return cs, new

    def _get_chunk(self, cs: str) -> bytes:
        if cs in self.chunks:
            return self.chunks[cs]
        if self.root:
            with open(os.path.join(self.root, "chunks", cs), "rb") as f:
                data = f.read()
            self.chunks[cs] = data
            return data
        raise KeyError(cs)

    def _put_blob(self, data: bytes) -> Tuple[List[str], int]:
        """Store a blob as content chunks; returns (chunk refs, new bytes)."""
        refs, new_bytes = [], 0
        for i in range(0, max(len(data), 1), CHUNK):
            piece = data[i:i + CHUNK]
            cs, new = self._put_chunk(piece)
            refs.append(cs)
            if new:
                new_bytes += len(piece)
        return refs, new_bytes

    def _get_blob(self, refs: List[str]) -> bytes:
        return b"".join(self._get_chunk(c) for c in refs)

    # -------------------------------------------------------------- snapshot
    def snapshot(self, job_id: str, step: int,
                 device_state_by_worker: Dict[int, Any],
                 host_state_by_worker: Dict[int, Dict],
                 files_by_worker: Optional[Dict[int, Dict[str, bytes]]] = None
                 ) -> SnapshotStats:
        """Take a consistent checkpoint.

        device_state_by_worker: worker -> pytree of arrays (P, O, ...).
        host_state_by_worker:   worker -> picklable host program state.
        files_by_worker:        worker -> {path: content} mutated local files
                                (tracked by the libc SA_Int, §4.4); deduped
                                by content checksum across workers.
        """
        prof = self.prof
        with prof.span("ckpt.put"):
            manifest: Dict = {"job": job_id, "step": step, "workers": {}}
            dev_logical = dev_stored = host_logical = host_stored = 0

            for w, tree in device_state_by_worker.items():
                leaves, treedef = jax.tree_util.tree_flatten(tree)
                entries = []
                for leaf in leaves:
                    with prof.span("ckpt.serialize"):
                        data = _leaf_bytes(leaf)
                    dev_logical += len(data)
                    with prof.span("ckpt.hash"):
                        refs, new = self._put_blob(data)
                    dev_stored += new
                    entries.append(refs)
                worker = manifest["workers"].setdefault(str(w), {})
                worker["device"] = entries
                worker["treedef"] = pickle.dumps(treedef).hex()
            prof.add("ckpt.bytes", dev_logical)
            prof.add("ckpt.bytes_new", dev_stored)

            for w, host in host_state_by_worker.items():
                data = pickle.dumps(host)
                host_logical += len(data)
                refs, new = self._put_blob(data)
                host_stored += new
                manifest["workers"].setdefault(str(w), {})["host"] = refs

            if files_by_worker:
                for w, files in files_by_worker.items():
                    fl = {}
                    for path, content in files.items():
                        refs, new = self._put_blob(content)
                        host_stored += new
                        fl[path] = refs
                    manifest["workers"].setdefault(str(w), {})["files"] = fl

            self.manifests.setdefault(job_id, []).append(manifest)
            if self.root:
                path = os.path.join(self.root, f"{job_id}.manifests.json")
                with open(path, "w") as f:
                    json.dump(self.manifests[job_id], f, default=str)
            return SnapshotStats(
                step=step, device_logical_bytes=dev_logical,
                device_stored_bytes=dev_stored, host_logical_bytes=host_logical,
                host_stored_bytes=host_stored,
                n_workers=len(device_state_by_worker))

    # --------------------------------------------------------------- restore
    def restore(self, job_id: str, step: Optional[int] = None
                ) -> Tuple[Dict[int, Any], Dict[int, Dict], int]:
        """Returns (device_state_by_worker, host_state_by_worker, step)."""
        with self.prof.span("restore.get"):
            snaps = self.manifests[job_id]
            manifest = snaps[-1] if step is None else \
                next(m for m in snaps if m["step"] == step)
            device, host = {}, {}
            for w, entry in manifest["workers"].items():
                treedef = pickle.loads(bytes.fromhex(entry["treedef"]))
                with self.prof.span("restore.read"):
                    leaves = [_leaf_from_bytes(self._get_blob(refs))
                              for refs in entry["device"]]
                device[int(w)] = jax.tree_util.tree_unflatten(treedef, leaves)
                host[int(w)] = pickle.loads(self._get_blob(entry["host"]))
            return device, host, manifest["step"]

    # ----------------------------------------------------------------- sizes
    def stored_bytes(self) -> int:
        return sum(len(v) for v in self.chunks.values())
