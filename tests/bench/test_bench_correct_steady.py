"""``correct`` of the steady cell on the CPU at a tiny size: a sound run
passes, and each fault planted in the timed path fails it."""
import pytest

import bench_tiny_cells as tc

CELL = "olmo-1b-l4.steady"


def test_sound_run_is_correct():
    out = tc.run(tc.tiny(CELL))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"tokens_per_s", "setup_s"} <= set(out["metrics"])
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault,caught", [
    ("unchanged", {"change_gap"}),
    ("half_batch", {"grad_gap"}),
])
def test_fault_is_not_correct(monkeypatch, fault, caught):
    tc.break_step(monkeypatch, fault)
    out = tc.run(tc.tiny(CELL))
    assert not out["correct"]
    assert caught <= tc.failed(out), out["checks"]
