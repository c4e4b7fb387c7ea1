"""The checkpoint store hashes each device leaf's 1 MiB chunks in place,
on a thread pool: the manifests, the chunk table, the stats and the
counters are those of the serialize-then-slice path it replaced (kept
here as the oracle), for every leaf layout the store can meet."""
import dataclasses
import hashlib
import io
import json
import os
import pickle

import jax
import ml_dtypes
import numpy as np
import pytest

from repro.core.checkpoint import CHUNK, CheckpointStore
from repro.utils.profiler import Profiler


class _OracleStore:
    """The store's write path before chunks were hashed in place:
    ``np.save`` into a buffer, 1 MiB slices, blake2b-128 one by one."""

    def __init__(self, root=None):
        self.root = root
        self.prof = Profiler()
        self.chunks = {}
        self.manifests = {}
        if root:
            os.makedirs(os.path.join(root, "chunks"), exist_ok=True)

    def _put_blob(self, data):
        refs, new_bytes = [], 0
        for i in range(0, max(len(data), 1), CHUNK):
            piece = data[i:i + CHUNK]
            cs = hashlib.blake2b(piece, digest_size=16).hexdigest()
            if cs not in self.chunks:
                self.chunks[cs] = piece
                new_bytes += len(piece)
                if self.root:
                    with open(os.path.join(self.root, "chunks", cs),
                              "wb") as f:
                        f.write(piece)
            refs.append(cs)
        return refs, new_bytes

    def snapshot(self, job_id, step, device_state_by_worker,
                 host_state_by_worker, files_by_worker=None):
        manifest = {"job": job_id, "step": step, "workers": {}}
        dev_logical = dev_stored = host_logical = host_stored = 0
        for w, tree in device_state_by_worker.items():
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            entries = []
            for leaf in leaves:
                buf = io.BytesIO()
                np.save(buf, np.asarray(leaf), allow_pickle=False)
                data = buf.getvalue()
                dev_logical += len(data)
                refs, new = self._put_blob(data)
                dev_stored += new
                entries.append(refs)
            worker = manifest["workers"].setdefault(str(w), {})
            worker["device"] = entries
            worker["treedef"] = pickle.dumps(treedef).hex()
        self.prof.add("ckpt.bytes", dev_logical)
        self.prof.add("ckpt.bytes_new", dev_stored)
        for w, host in host_state_by_worker.items():
            data = pickle.dumps(host)
            host_logical += len(data)
            refs, new = self._put_blob(data)
            host_stored += new
            manifest["workers"].setdefault(str(w), {})["host"] = refs
        for w, files in (files_by_worker or {}).items():
            fl = {}
            for path, content in files.items():
                refs, new = self._put_blob(content)
                host_stored += new
                fl[path] = refs
            manifest["workers"].setdefault(str(w), {})["files"] = fl
        self.manifests.setdefault(job_id, []).append(manifest)
        if self.root:
            with open(os.path.join(self.root, f"{job_id}.manifests.json"),
                      "w") as f:
                json.dump(self.manifests[job_id], f, default=str)
        return (step, dev_logical, dev_stored, host_logical, host_stored,
                len(device_state_by_worker))


def _npy_size(arr) -> int:
    buf = io.BytesIO()
    np.save(buf, arr, allow_pickle=False)
    return len(buf.getvalue())


def _exact_chunk_leaf():
    """A float32 leaf whose npy blob is exactly one chunk."""
    header = _npy_size(np.zeros(1024, np.float32)) - 4096
    leaf = np.arange((CHUNK - header) // 4, dtype=np.float32)
    assert _npy_size(leaf) == CHUNK
    return leaf


def _leaves(seed):
    """Every layout: (name, leaf, hashed in place)."""
    rng = np.random.default_rng(seed)
    pattern = rng.standard_normal(CHUNK // 4).astype(np.float32)
    return [
        ("f32_3.5MiB", rng.standard_normal((896, 1024)).astype(np.float32),
         True),
        ("f32_one_chunk", _exact_chunk_leaf() + seed, True),
        ("i32_scalar", np.asarray(np.int32(seed)), True),
        ("empty", np.zeros((0, 3), np.float32), True),
        ("bf16", rng.standard_normal((700, 1024)).astype(ml_dtypes.bfloat16),
         True),
        ("fortran", np.asfortranarray(
            rng.standard_normal((1500, 700)).astype(np.float32)), True),
        ("strided", rng.standard_normal((1024, 1024)).astype(
            np.float32)[:, ::2], False),
        ("permuted", rng.standard_normal((6, 400, 300)).astype(
            np.float32).transpose(0, 2, 1), False),
        ("repeating", np.tile(pattern, 4), True),
    ]


def _tree(seed):
    return {name: leaf for name, leaf, _ in _leaves(seed)}


def _pair(root_new=None, root_old=None):
    return (CheckpointStore(root=root_new, profiler=Profiler()),
            _OracleStore(root=root_old))


def _run_both(new, old, snapshots):
    for args in snapshots:
        stats = new.snapshot(*args)
        assert dataclasses.astuple(stats) == old.snapshot(*args)


def _churn_snapshots():
    """Two workers that share leaf objects, then a second snapshot in
    which one leaf changed and the rest are the same objects."""
    first = _tree(1)
    second = dict(first)
    second["f32_3.5MiB"] = first["f32_3.5MiB"] + 1.0
    files = {0: {"/w/log": b"x" * (CHUNK + 5)}, 1: {"/w/log": b"x" * 7}}
    return [
        ("job", 3, {0: first, 1: first}, {0: {"rank": 0}, 1: {"rank": 1}},
         files),
        ("job", 4, {0: second, 1: second}, {0: {"rank": 0}, 1: {"rank": 1}},
         None),
    ]


def test_manifests_chunks_and_stats_equal_the_oracle():
    new, old = _pair()
    _run_both(new, old, _churn_snapshots())
    assert new.manifests == old.manifests
    assert new.chunks == old.chunks
    assert list(new.chunks) == list(old.chunks)       # inserted in order


def test_counters_equal_the_oracle():
    new, old = _pair()
    _run_both(new, old, _churn_snapshots())
    for name in ("ckpt.bytes", "ckpt.bytes_new"):
        assert new.prof.counters[name] == old.prof.counters[name]
    in_place = sum(_npy_size(leaf) for _, leaf, inplace in _leaves(1)
                   if inplace)
    # two workers, two snapshots, the same layouts each time
    assert new.prof.counters["ckpt.bytes_inplace"] == 4 * in_place
    assert new.prof.counters["ckpt.bytes_inplace"] < \
        new.prof.counters["ckpt.bytes"]


@pytest.mark.parametrize("name", [name for name, _, _ in _leaves(0)])
def test_each_layout_alone_equals_the_oracle(name):
    leaf = dict((n, x) for n, x, _ in _leaves(7))[name]
    new, old = _pair()
    args = ("job", 0, {0: {"x": leaf}}, {0: {}})
    _run_both(new, old, [args])
    assert new.manifests == old.manifests and new.chunks == old.chunks


def test_repeating_chunks_are_stored_once():
    leaf = dict((n, x) for n, x, _ in _leaves(2))["repeating"]
    new, _ = _pair()
    stats = new.snapshot("job", 0, {0: {"x": leaf}}, {0: {}})
    refs = new.manifests["job"][0]["workers"]["0"]["device"][0]
    assert len(refs) == 5 and refs[1] == refs[2] == refs[3]
    assert stats.device_stored_bytes == stats.device_logical_bytes - 2 * CHUNK


def test_restore_round_trip_bit_exact():
    new, _ = _pair()
    tree = _tree(3)
    new.snapshot("job", 9, {0: tree, 1: tree}, {0: {"r": 0}, 1: {"r": 1}})
    device, host, step = new.restore("job")
    assert step == 9 and host == {0: {"r": 0}, 1: {"r": 1}}
    for w in (0, 1):
        for name, leaf in tree.items():
            got = device[w][name]
            assert got.shape == leaf.shape
            assert got.tobytes() == leaf.tobytes(), name


def test_writes_after_snapshot_do_not_reach_the_store():
    new, _ = _pair()
    tree = {name: np.array(leaf, order="K")
            for name, leaf in _tree(4).items()}
    kept = {name: leaf.copy() for name, leaf in tree.items()}
    new.snapshot("job", 0, {0: tree}, {0: {}})
    for leaf in tree.values():
        leaf.fill(7)
    device, _, _ = new.restore("job")
    for name, leaf in kept.items():
        assert device[0][name].tobytes() == leaf.tobytes(), name


def test_disk_backed_store_writes_the_same_files(tmp_path):
    new_root, old_root = tmp_path / "new", tmp_path / "old"
    new, old = _pair(str(new_root), str(old_root))
    _run_both(new, old, _churn_snapshots())

    def files(root):
        out = {}
        for dirpath, _, names in os.walk(root):
            for n in names:
                path = os.path.join(dirpath, n)
                with open(path, "rb") as f:
                    out[os.path.relpath(path, root)] = f.read()
        return out

    assert files(new_root) == files(old_root)
    fresh = CheckpointStore(root=str(new_root))
    fresh.manifests = new.manifests
    device, _, _ = fresh.restore("job", step=3)
    assert device[1]["fortran"].tobytes() == _tree(1)["fortran"].tobytes()


def test_pool_is_made_on_first_use_and_kept():
    store = CheckpointStore()
    store.snapshot("job", 0, {0: {"s": np.float32(1.0)}}, {0: {}})
    assert store._pool is None              # one-chunk leaves skip it
    store.snapshot("job", 1, {0: _tree(5)}, {0: {}})
    pool = store._pool
    assert pool is not None
    store.snapshot("job", 2, {0: _tree(6)}, {0: {}})
    assert store._pool is pool
