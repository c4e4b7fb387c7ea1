"""A restore builds each distinct device blob once, straight from its
chunks into one array: every layout comes back as the ``np.load`` of the
joined blob (kept here as the oracle) gives it, in memory and from disk,
and workers with the same blob share one array."""
import io

import ml_dtypes
import numpy as np
import pytest

from repro.core.checkpoint import CHUNK, CheckpointStore
from repro.utils.profiler import Profiler


def _npy_size(arr) -> int:
    buf = io.BytesIO()
    np.save(buf, arr, allow_pickle=False)
    return len(buf.getvalue())


def _sized(total, dtype=np.uint8):
    """A 1-D leaf whose npy blob is exactly ``total`` bytes."""
    n = (total - _npy_size(np.zeros(4096, dtype)) + 4096 * np.dtype(
        dtype).itemsize) // np.dtype(dtype).itemsize
    leaf = (np.arange(n) % 251).astype(dtype)
    assert _npy_size(leaf) == total
    return leaf


def _layouts():
    rng = np.random.default_rng(17)
    return {
        "c_order": rng.standard_normal((900, 1100)).astype(np.float32),
        "f_order": np.asfortranarray(
            rng.standard_normal((1500, 700)).astype(np.float32)),
        "neither_order": rng.standard_normal((1024, 1024)).astype(
            np.float32)[:, ::2],
        "permuted": rng.standard_normal((6, 400, 300)).astype(
            np.float32).transpose(0, 2, 1),
        "zero_d": np.asarray(np.int32(-5)),
        "empty": np.zeros((0, 3), np.float32),
        "one_chunk": _sized(CHUNK),
        "many_chunks": rng.standard_normal((7, 1 << 18)).astype(np.float32),
        "last_chunk_1_byte": _sized(3 * CHUNK + 1),
        "bf16": rng.standard_normal((700, 1024)).astype(ml_dtypes.bfloat16),
    }


def _store(tmp_path, backing):
    if backing == "memory":
        return CheckpointStore(profiler=Profiler())
    return CheckpointStore(root=str(tmp_path), profiler=Profiler())


def _fresh(store):
    """The store itself, or for a disk-backed one a new store over the
    same root, so that every chunk is read back from its file."""
    if store.root is None:
        return store
    fresh = CheckpointStore(root=store.root, profiler=Profiler())
    fresh.manifests = store.manifests
    return fresh


@pytest.mark.parametrize("backing", ["memory", "disk"])
@pytest.mark.parametrize("name", list(_layouts()))
def test_each_layout_is_the_np_load_of_its_blob(tmp_path, backing, name):
    leaf = _layouts()[name]
    store = _store(tmp_path, backing)
    store.snapshot("job", 0, {0: {"x": leaf}}, {0: {}})
    refs = store.manifests["job"][0]["workers"]["0"]["device"][0]
    assert (len(refs) == 1) == (name in ("zero_d", "empty", "one_chunk"))
    store = _fresh(store)
    oracle = np.load(io.BytesIO(b"".join(store._get_chunk(c) for c in refs)),
                     allow_pickle=False)
    got = store.restore("job")[0][0]["x"]
    assert type(got) is np.ndarray
    assert got.shape == oracle.shape and got.dtype == oracle.dtype
    assert got.flags.c_contiguous == oracle.flags.c_contiguous
    assert got.flags.f_contiguous == oracle.flags.f_contiguous
    assert got.tobytes(order="A") == oracle.tobytes(order="A")
    assert got.tobytes() == np.asarray(leaf).tobytes()
    assert got.flags.writeable == oracle.flags.writeable
    if name == "last_chunk_1_byte":
        assert len(store._get_chunk(refs[-1])) == 1
    assert store.prof.counters["restore.bytes"] == _npy_size(leaf)
    assert store.prof.counters["restore.bytes_built"] == _npy_size(leaf)


def test_workers_with_equal_refs_share_one_array():
    store = CheckpointStore(profiler=Profiler())
    tree = {k: _layouts()[k] for k in ("c_order", "f_order", "zero_d")}
    store.snapshot("job", 0, {0: tree, 1: tree, 2: tree},
                   {w: {"rank": w} for w in range(3)})
    device, host, _ = store.restore("job")
    assert host == {w: {"rank": w} for w in range(3)}
    for name, leaf in tree.items():
        assert device[0][name] is device[1][name] is device[2][name]
        assert device[0][name].tobytes() == leaf.tobytes()
    assert store.prof.counts["restore.read"] == 3
    total = 3 * sum(_npy_size(leaf) for leaf in tree.values())
    assert store.prof.counters["restore.bytes"] == total
    assert store.prof.counters["restore.bytes_built"] == total // 3


def test_workers_with_different_states_get_distinct_arrays():
    store = CheckpointStore(profiler=Profiler())
    a = _layouts()["many_chunks"]
    b = a.copy()
    b[3, 5] += 1.0                  # one chunk differs, the rest are shared
    shared = _layouts()["f_order"]
    store.snapshot("job", 0, {0: {"x": a, "y": shared},
                              1: {"x": b, "y": shared}}, {0: {}, 1: {}})
    device, _, _ = store.restore("job")
    assert device[0]["x"] is not device[1]["x"]
    assert device[0]["x"].tobytes() == a.tobytes()
    assert device[1]["x"].tobytes() == b.tobytes()
    assert device[0]["y"] is device[1]["y"]
    c = store.prof.counters
    assert c["restore.bytes"] == 2 * (_npy_size(a) + _npy_size(shared))
    assert c["restore.bytes_built"] == 2 * _npy_size(a) + _npy_size(shared)


def test_each_restore_builds_its_own_arrays():
    store = CheckpointStore(profiler=Profiler())
    leaf = _layouts()["c_order"]
    store.snapshot("job", 0, {0: {"x": leaf}}, {0: {}})
    first = store.restore("job")[0][0]["x"]
    second = store.restore("job")[0][0]["x"]
    assert first is not second and first.tobytes() == second.tobytes()
    assert store.prof.counters["restore.bytes_built"] == \
        store.prof.counters["restore.bytes"] == 2 * _npy_size(leaf)
