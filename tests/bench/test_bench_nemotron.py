"""``nemotron3-nano-l7e8.steady`` on the CPU at a tiny size, through the
harness as committed: a sound run is ``correct`` against the plain
reference, each fault planted in the timed path fails it; the readers of
its routing counters and of its expert kernel's trace."""
import copy
import types

import pytest

import bench_tiny_cells as tc
from bench import harness, spec
from bench.references.common import Matmul
from bench.traffic import job_seed

CELL = "nemotron3-nano-l7e8.steady"


def tiny() -> spec.Cell:
    """Every width shrunk, the pattern, the held share of the experts and
    the traffic kept."""
    cell = spec.cell(CELL)
    cfg = copy.deepcopy(cell.config)
    m = cfg["model"]
    m.update(dtype="float32", d_model=64, num_heads=4, num_kv_heads=2,
             head_dim=16, d_ff=32, vocab_size=256)
    m["ssm"].update(state_dim=16, head_dim=16, num_heads=8, n_groups=2,
                    chunk_size=16)
    m["moe"].update(num_experts=32, top_k=3, num_held=2, shared_expert_ff=48)
    cfg["train"].update(global_batch=4, seq_len=64)
    cell.config = cfg
    return cell


def test_experts_held_is_the_model_share():
    cfg = spec.config("nemotron3-nano-l7e8")
    moe = cfg["model"]["moe"]
    assert cfg["experts_held"] == moe["num_held"] == 8
    assert cfg["n_routed_experts"] == moe["num_experts"] == 128
    assert cfg["hybrid_override_pattern"] == cfg["model"]["layer_pattern"]
    assert cfg["num_hidden_layers"] == cfg["model"]["num_layers"] == 7
    assert cfg["vocab_size"] == cfg["model"]["vocab_size"]


def test_sound_run_is_correct():
    out = tc.run(tiny())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    counters = out["info"]["program"]["counters"]
    assert counters["moe.block_steps"] == 3 * out["attempted"]     # 3 "E"
    assert 0 < counters["moe.rows"] <= counters["moe.rows_peak"] * 2


@pytest.mark.parametrize("fault,caught", [
    ("unchanged", {"change_gap"}),
    ("half_batch", {"grad_gap"}),
])
def test_fault_is_not_correct(monkeypatch, fault, caught):
    tc.break_step(monkeypatch, fault)
    out = tc.run(tiny())
    assert not out["correct"]
    assert caught <= tc.failed(out), out["checks"]


@pytest.mark.parametrize("kind", ["control", "half_batch"])
def test_controls_fail_the_limits(kind):
    """The float32 reference one precision lower (float8 operands), and
    given half of each batch, each fails the committed limits."""
    cell = tiny()
    tr = cell.traffic
    job = next(j for j in tr["jobs"] if j["id"] == tr["follow"]["job"])
    seed, total = job_seed(41, job["id"]), int(job["total_steps"])
    ref = harness.reference_readings(cell, seed, total)
    kw = ({"mm": Matmul("float8_e4m3fn")} if kind == "control"
          else {"rows": cell.config["train"]["global_batch"] // 2})
    gaps = harness.gaps(harness.reference_readings(cell, seed, total, **kw), ref)
    assert any(gaps[k] > cell.limits[k] for k in gaps if k in cell.limits), gaps


def _ctx(counters, op_time=None):
    cell = spec.cell(CELL)
    trace = None if op_time is None else types.SimpleNamespace(
        devices={"/device:TPU:0": {"op_time": op_time}})
    return {"cell": cell, "trace": trace,
            "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
            "program": {"totals": {}, "counts": {}, "counters": counters}}


def test_moe_imbalance_reader():
    read = spec.metric_reader("moe_imbalance")
    assert read(_ctx({})) is None
    # 3 block-steps of 8 held experts: the busiest took 300 rows each time
    got = read(_ctx({"moe.rows": 3 * 1600, "moe.rows_peak": 3 * 300,
                     "moe.block_steps": 3}))
    assert got == pytest.approx(300 * 8 / 1600)


def test_gmm_roofline_reader():
    read = spec.metric_reader("gmm_roofline")
    counts = spec.counts("nemotron_h")
    model = spec.config("nemotron3-nano-l7e8")["model"]
    rows, blocks = 3 * 768.0, 3
    ctx = _ctx({"moe.rows": rows, "moe.block_steps": blocks}, {})
    assert read(ctx) is None                        # no kernel in the trace
    least = counts.gmm_roofline_s(model, rows, blocks, ctx["peak"])
    # at 768 rows a pass reads the 8 held experts' weights: bound by bytes
    d, f = 2688, 1856
    one = counts.gmm_bytes(768, d, f, 8) / 819e9
    assert one > counts.gmm_flops(768, d, f) / 197e12
    assert least == pytest.approx(blocks * 8 * one)
    ops = {"gmm custom-call": least * 1e9, "gmm.7 custom-call": least * 1e9,
           "tgmm.2 custom-call": 2 * least * 1e9,
           "custom-call.9 custom-call": 3e9,      # another custom call
           "fusion.3 fusion/kLoop": 5e9}
    assert read(_ctx({"moe.rows": rows, "moe.block_steps": blocks}, ops)) \
        == pytest.approx(25.0)
