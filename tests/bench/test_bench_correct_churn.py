"""``correct`` of the churn cell on the CPU at a tiny size: a sound run
passes with its preempt, restore and resize, and faults planted in the
checkpoint, the restore and the splice-2 step each fail it."""
import numpy as np
import pytest

import bench_tiny_cells as tc
from repro.core import checkpoint, elastic

CELL = "mamba2-130m.churn"


def test_sound_run_is_correct():
    out = tc.run(tc.tiny(CELL))
    assert out["correct"], out["checks"]
    # set-up's loop preempted, restored (checked against the checkpoint)
    # and resized the followed job at the planned steps
    assert out["checks"]["restore_mismatch"]["value"] == 0
    assert out["checks"]["trajectory_mismatch"]["value"] == 0
    assert {"tokens_per_s.churn", "setup_s"} <= set(out["metrics"])


def test_altered_restore(monkeypatch):
    orig = checkpoint.CheckpointStore.restore

    def restore(self, job_id, step=None):
        device, host, at = orig(self, job_id, step)
        leaf = device[0]["params"]["embed"]
        leaf.flat[0] += 1.0       # one word changed where the state is read back
        return device, host, at

    monkeypatch.setattr(checkpoint.CheckpointStore, "restore", restore)
    out = tc.run(tc.tiny(CELL))
    assert not out["correct"]
    assert "restore_mismatch" in tc.failed(out), out["checks"]


@pytest.mark.parametrize("fault", ["splice2_one_slice", "unchanged_at_restore"])
def test_broken_splice_or_resume(monkeypatch, fault):
    orig = elastic.build_train_step

    def build(cfg, tcfg, splice=1, **kw):
        step = orig(cfg, tcfg, splice=splice, **kw)
        if splice != 2:
            return step
        if fault == "splice2_one_slice":
            # the splice-2 step runs one of its two time-slices
            one = orig(cfg, tcfg, splice=1, **kw)

            def broken(state, batch, flags=None):
                half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
                return one(state, half, flags)
            return broken

        def unchanged(state, batch, flags=None):
            new, metrics = step(state, batch, flags)
            return dict(state, step=new["step"]), metrics
        return unchanged

    monkeypatch.setattr(elastic, "build_train_step", build)
    out = tc.run(tc.tiny(CELL))
    assert not out["correct"]
    assert tc.failed(out) & {"change_gap", "loss_gap", "grad_gap"}, out["checks"]


@pytest.mark.parametrize("fault,caught", [
    ("unchanged", {"change_gap"}),
    ("half_batch", {"grad_gap"}),
])
def test_fault_is_not_correct(monkeypatch, fault, caught):
    tc.break_step(monkeypatch, fault)
    out = tc.run(tc.tiny(CELL))
    assert not out["correct"]
    assert caught <= tc.failed(out), out["checks"]


def test_restore_fingerprint_sees_one_word():
    from bench.probes import fingerprint
    a = {"x": np.arange(12, dtype=np.float32).reshape(3, 4),
         "n": np.int32(7)}
    b = {"x": a["x"].copy(), "n": np.int32(7)}
    b["x"][2, 3] = np.nextafter(b["x"][2, 3], np.float32(99))
    fa, fb = np.asarray(fingerprint(a)), np.asarray(fingerprint(b))
    assert fa[0] == np.asarray(fingerprint(a))[0]
    assert (fa != fb).tolist() == [False, True]
