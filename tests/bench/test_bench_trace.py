"""The reduction from a profiler trace to the benchmark's device numbers,
on synthetic intervals and on a small trace recorded on a TPU v5e."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace  # noqa: E402

RECORDED = ROOT / "bench" / "data" / "olmo-1b-l4.steady.xplane.pb.gz"


def test_union_merges_overlaps():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_self_times_subtract_nested_events():
    got = trace.self_times([(0, 10, "loop"), (1, 3, "a"), (4, 6, "b"),
                            (4.5, 5, "c"), (11, 12, "a")])
    assert got == {"loop": 6, "a": 3, "b": 1.5, "c": 0.5}


def test_op_name_keeps_instruction_and_kind():
    text = ("%fusion.574 = (f32[4,16,512]{2,1,0:T(8,128)S(1)}, f32[4]{0}) "
            "fusion(f32[512,512]{0,1:T(8,128)S(1)} %x), kind=kOutput, "
            "calls=%fused_computation.530")
    assert trace.op_name(text) == "fusion.574 fusion/kOutput"
    assert trace.op_name("%while.3 = (s32[]{:T(128)}) while((s32[]) %t)") \
        == "while.3 while"


def _reduced():
    ns = 1e6  # 1 ms in ns
    dev = {"busy": [(10 * ns, 40 * ns), (60 * ns, 90 * ns)],
           "op_time": {"dot": 50 * ns, "add": 10 * ns},
           "modules": {"jit_train_step": [(10 * ns, 40 * ns),
                                          (60 * ns, 90 * ns)]}}
    spans = [("bench:window", 0, 100 * ns), ("bench:apply", 38 * ns, 62 * ns),
             ("bench:store_put", 45 * ns, 55 * ns)]
    return trace.Reduced((0, 100 * ns), {"/device:TPU:0": dev}, spans)


def test_reduced_numbers():
    r = _reduced()
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.06)
    assert r.idle_share() == pytest.approx(0.4)
    assert r.program_ms("jit_train_step") == pytest.approx(30.0)
    assert r.program_ms("jit_other") is None
    assert r.top_ops() == [["dot", pytest.approx(0.05)],
                           ["add", pytest.approx(0.01)]]
    gaps = r.idle_gaps()
    assert gaps[0] == ["apply/store_put", pytest.approx(0.02)]
    assert [g[0] for g in gaps[1:]] == ["none", "none"]


def test_recorded_trace():
    """One olmo-1b-l4 step at 4 x 2048, traced by ``bench/run.py --trace 1``
    on one v5e; the numbers are those its run printed."""
    r = trace.reduce(trace.load(str(RECORDED)))
    assert list(r.devices) == ["/device:TPU:0"]
    assert r.window_s == pytest.approx(EXPECTED["window_s"], rel=1e-9)
    assert r.busy_s == pytest.approx(EXPECTED["busy_s"], rel=1e-9)
    assert r.program_ms("jit_train_step") == pytest.approx(
        EXPECTED["step_ms"], rel=1e-9)
    assert 0 < r.idle_share() < 1
    assert r.top_ops()[0][0] == EXPECTED["top_op"]
    assert [g[0] for g in r.idle_gaps()][:1] == [EXPECTED["first_gap"]]


EXPECTED = {
    "window_s": 0.346475601,
    "busy_s": 0.335920804,
    "step_ms": 335.927638,
    "top_op": "select_add_fusion.7 fusion/kOutput",
    "first_gap": "step",
}
