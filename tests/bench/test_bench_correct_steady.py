"""``correct`` of the steady cells on the CPU at a tiny size: a sound run
passes, and each fault planted in the timed path fails it."""
import pytest

import bench_tiny_cells as tc

CELLS = ["olmo-1b-l4.steady", "mamba2-130m.steady"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = tc.run(tc.tiny(cell))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"tokens_per_s", "setup_s"} <= set(out["metrics"])
    assert list(out)[-1] == "checks"
    # the program's spans over the window, as the result line keeps them
    calls, seconds = out["info"]["program"]["spans"]["decide"]
    assert calls > 0 and seconds > 0


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault,caught", [
    ("unchanged", {"change_gap"}),
    ("half_batch", {"grad_gap"}),
])
def test_fault_is_not_correct(monkeypatch, fault, caught, cell):
    tc.break_step(monkeypatch, fault)
    out = tc.run(tc.tiny(cell))
    assert not out["correct"]
    assert caught <= tc.failed(out), out["checks"]
