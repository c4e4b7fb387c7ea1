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

A device leaf's blob is the one ``np.save`` writes: the npy header, then
the data.  The store never builds that blob: it hashes each 1 MiB chunk of
it straight from the leaf's own buffer (one contiguous copy only where the
leaf's memory is not already ``np.save``'s data order), on a thread pool,
and copies only the chunks new to the store.  A restore builds each
distinct device blob once, straight from its chunks into one array, on
the same pool; workers whose blobs are the same share it.
"""
from __future__ import annotations

import dataclasses
import io
import json
import os
import pickle
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from numpy.lib import format as npy_format

from repro.utils.hashing import chunk_digest
from repro.utils.profiler import Profiler

CHUNK = 1 << 20     # 1 MiB content chunks (page-dedup granularity)
HASH_THREADS = 8    # cap on the store's pool (blake2b and numpy copies
                    # release the GIL)
_HEAD_MAX = 1 << 16  # bytes that hold any npy header np.load accepts


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
    device leaf of each worker, ``ckpt.serialize`` (the npy header and a
    view of the leaf's buffer, or its one contiguous copy) and
    ``ckpt.hash`` (on the pool, the chunks' blake2b and then the copy of
    those new to the store; on the caller, the dedup and insertion);
    counters ``ckpt.bytes``, ``ckpt.bytes_new`` and ``ckpt.bytes_inplace``
    (device bytes of the npy blobs; of those, new to the store; of those,
    hashed straight from a leaf's own buffer).  ``restore.get`` (a whole
    restore) holding one ``restore.read`` per worker: the builds of that
    worker's device leaves not already built in this restore; counters
    ``restore.bytes`` and ``restore.bytes_built`` (device bytes of the npy
    blobs the manifest names, over all workers; of those, copied into a
    newly built array)."""

    def __init__(self, root: Optional[str] = None,
                 profiler: Optional[Profiler] = None):
        self.root = root
        self.prof = profiler if profiler is not None else Profiler()
        # a put's new chunks are views of one buffer the store owns;
        # chunks read back from disk are bytes
        self.chunks: Dict[str, bytes | memoryview] = {}
        self.manifests: Dict[str, List[Dict]] = {}     # job -> snapshots
        self._pool: Optional[ThreadPoolExecutor] = None
        if root:
            os.makedirs(os.path.join(root, "chunks"), exist_ok=True)

    # ---------------------------------------------------------------- chunks
    def _map(self, fn, items: Sequence) -> List:
        """``fn`` over ``items`` in order; on the store's pool unless one
        item makes the whole work."""
        if len(items) <= 1:
            return [fn(i) for i in items]
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=min(os.cpu_count() or 1, HASH_THREADS),
                thread_name_prefix="ckpt-hash")
        return list(self._pool.map(fn, items))

    def _npy_parts(self, leaf) -> Tuple[bytes, memoryview, bool]:
        """``np.save``'s bytes of ``leaf``, unjoined: (npy header, data, in
        place).  The data is a view of the leaf's own buffer where that
        buffer already holds ``np.save``'s data bytes (C order, or F order
        through ``.T``); any other leaf takes one C-order copy (``in
        place`` False), made on the pool in slabs along its first axis."""
        arr = np.asarray(leaf)
        head = io.BytesIO()
        npy_format._write_array_header(
            head, npy_format.header_data_from_array_1_0(arr))
        if arr.flags.c_contiguous:
            flat, in_place = arr, True
        elif arr.flags.f_contiguous:
            flat, in_place = arr.T, True
        else:
            flat, in_place = np.empty(arr.shape, arr.dtype), False
            rows = -(-len(arr) // HASH_THREADS)
            self._map(lambda i: np.copyto(flat[i:i + rows], arr[i:i + rows]),
                      range(0, len(arr), rows))
        return head.getvalue(), memoryview(flat.reshape(-1).view(np.uint8)), \
            in_place

    def _get_chunk(self, cs: str) -> bytes | memoryview:
        if cs in self.chunks:
            return self.chunks[cs]
        if self.root:
            with open(os.path.join(self.root, "chunks", cs), "rb") as f:
                data = f.read()
            self.chunks[cs] = data
            return data
        raise KeyError(cs)

    def _put_blob(self, data, head: bytes = b"") -> Tuple[List[str], int]:
        """Store the blob ``head + data`` as content chunks without joining
        it: chunk k is bytes [k * CHUNK, (k + 1) * CHUNK) of the blob.
        The pool hashes the chunks; the caller picks, in chunk order, those
        new to the store; the pool copies them into one buffer the store
        owns, and each is stored as a view of it.  Returns (chunk refs,
        new bytes)."""
        data = memoryview(data)
        n_head, size = len(head), len(head) + data.nbytes

        def parts(k):
            a, b = k * CHUNK, min((k + 1) * CHUNK, size)
            if a >= n_head:
                return (data[a - n_head:b - n_head],)
            return head[a:b], data[:max(b - n_head, 0)]

        refs = self._map(lambda k: chunk_digest(*parts(k)),
                         range(max(-(-size // CHUNK), 1)))
        new, seen, at = [], set(), [0]
        for k, cs in enumerate(refs):     # in chunk order, as inserted
            if cs not in self.chunks and cs not in seen:
                seen.add(cs)
                new.append(k)
                at.append(at[-1] + min(CHUNK, size - k * CHUNK))
        # one buffer for the new chunks: numpy allocates it in huge pages
        # and copies into it with the GIL released
        buf = np.empty(at[-1], np.uint8)

        def copy(i):
            pos = at[i]
            for part in parts(new[i]):
                buf[pos:pos + len(part)] = np.frombuffer(part, np.uint8)
                pos += len(part)

        self._map(copy, range(len(new)))
        view = memoryview(buf)
        for i, k in enumerate(new):
            piece = view[at[i]:at[i + 1]]
            self.chunks[refs[k]] = piece
            if self.root:
                with open(os.path.join(self.root, "chunks", refs[k]),
                          "wb") as f:
                    f.write(piece)
        return refs, at[-1]

    def _get_blob(self, refs: List[str]) -> bytes:
        return b"".join(self._get_chunk(c) for c in refs)

    def _build_leaf(self, refs: List[str]) -> Tuple[np.ndarray, int]:
        """The array ``np.load`` gives for the npy blob of chunks ``refs``,
        built with one copy of each chunk, on the pool.  Returns (array,
        blob bytes)."""
        chunks = [self._get_chunk(cs) for cs in refs]
        head = io.BytesIO(chunks[0][:_HEAD_MAX])
        read_header = {(1, 0): npy_format.read_array_header_1_0,
                       (2, 0): npy_format.read_array_header_2_0}
        shape, fortran, dtype = read_header[npy_format.read_magic(head)](head)
        n_head = head.tell()
        # _npy_parts writes headers far shorter than a chunk
        assert n_head <= len(chunks[0])
        arr = np.empty(shape, dtype, order="F" if fortran else "C")
        flat = (arr.T if fortran else arr).reshape(-1).view(np.uint8)
        size = sum(len(c) for c in chunks)
        assert size == n_head + flat.nbytes, (size, n_head, flat.nbytes)

        def copy(k):
            piece = np.frombuffer(chunks[k], np.uint8)[n_head if k == 0 else 0:]
            at = max(k * CHUNK - n_head, 0)
            flat[at:at + len(piece)] = piece

        self._map(copy, range(len(chunks)))
        return arr, size

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
            dev_logical = dev_stored = dev_inplace = 0
            host_logical = host_stored = 0

            for w, tree in device_state_by_worker.items():
                leaves, treedef = jax.tree_util.tree_flatten(tree)
                entries = []
                for leaf in leaves:
                    with prof.span("ckpt.serialize"):
                        head, data, in_place = self._npy_parts(leaf)
                    size = len(head) + data.nbytes
                    dev_logical += size
                    dev_inplace += size if in_place else 0
                    with prof.span("ckpt.hash"):
                        refs, new = self._put_blob(data, head)
                    dev_stored += new
                    entries.append(refs)
                worker = manifest["workers"].setdefault(str(w), {})
                worker["device"] = entries
                worker["treedef"] = pickle.dumps(treedef).hex()
            prof.add("ckpt.bytes", dev_logical)
            prof.add("ckpt.bytes_new", dev_stored)
            prof.add("ckpt.bytes_inplace", dev_inplace)

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
        """Returns (device_state_by_worker, host_state_by_worker, step).

        A device blob (the same chunk refs, so the same bytes) is built
        once per restore: every leaf that names it, in any worker, is that
        one array, so a write through one worker's tree shows in the
        others'."""
        prof = self.prof
        with prof.span("restore.get"):
            snaps = self.manifests[job_id]
            manifest = snaps[-1] if step is None else \
                next(m for m in snaps if m["step"] == step)
            device, host = {}, {}
            built: Dict[Tuple[str, ...], Tuple[np.ndarray, int]] = {}
            total = fresh = 0
            for w, entry in manifest["workers"].items():
                treedef = pickle.loads(bytes.fromhex(entry["treedef"]))
                leaves = []
                with prof.span("restore.read"):
                    for refs in entry["device"]:
                        key = tuple(refs)
                        if key not in built:
                            built[key] = self._build_leaf(refs)
                            fresh += built[key][1]
                        leaf, size = built[key]
                        total += size
                        leaves.append(leaf)
                device[int(w)] = jax.tree_util.tree_unflatten(treedef, leaves)
                host[int(w)] = pickle.loads(self._get_blob(entry["host"]))
            prof.add("restore.bytes", total)
            prof.add("restore.bytes_built", fresh)
            return device, host, manifest["step"]

    # ----------------------------------------------------------------- sizes
    def stored_bytes(self) -> int:
        return sum(len(v) for v in self.chunks.values())
