"""A configuration file's ``model`` block loads into the program's
``ModelConfig`` by the field types alone: every sub-config that is a
dataclass (``moe``, ``ssm``, ...) is built, lists become tuples, and an
unknown key raises.  A mixture-of-experts configuration, which no
committed file holds, runs set-up through the harness."""
import dataclasses
import sys
from pathlib import Path
from typing import Optional, Tuple

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import bench_tiny_cells as tc  # noqa: E402
from bench import harness  # noqa: E402
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig  # noqa: E402

MODEL = {
    "name": "hybrid-x", "arch_type": "hybrid", "num_layers": 2, "d_model": 64,
    "num_heads": 4, "num_kv_heads": 2, "d_ff": 128, "vocab_size": 256,
    "moe": {"num_experts": 8, "top_k": 2, "shared_expert_ff": 96},
    "ssm": {"state_dim": 16, "head_dim": 16, "expand": 2, "chunk_size": 16},
}


def test_sub_configs_built_by_type():
    cfg = harness.model_config({"model": MODEL})
    want = ModelConfig(
        name="hybrid-x", arch_type="hybrid", num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
        moe=MoEConfig(num_experts=8, top_k=2, shared_expert_ff=96),
        ssm=SSMConfig(state_dim=16, head_dim=16, expand=2, chunk_size=16))
    assert cfg == want
    assert hash(cfg) == hash(want)


@dataclasses.dataclass(frozen=True)
class _Inner:
    sizes: Tuple[int, ...] = ()


@dataclasses.dataclass(frozen=True)
class _Outer:
    pattern: Tuple[str, ...] = ()
    inner: Optional[_Inner] = None


def test_lists_become_tuples():
    got = harness.build(_Outer, {"pattern": ["M", "E", "*"],
                                 "inner": {"sizes": [[1, 2], [3]]}})
    assert got == _Outer(("M", "E", "*"), _Inner(((1, 2), (3,))))
    hash(got)


@pytest.mark.parametrize("where", ["model", "moe"])
def test_unknown_key_raises(where):
    model = {**MODEL, "moe": dict(MODEL["moe"])}
    (model if where == "model" else model["moe"])["no_such_key"] = 1
    with pytest.raises(TypeError, match="no_such_key"):
        harness.model_config({"model": model})


def test_moe_config_runs_set_up():
    """A configuration with a ``moe`` block builds its state and runs the
    followed steps through the harness, with no change to ``bench/``."""
    cell = tc.tiny("olmo-1b-l4.steady")
    cell.config["model"].update(
        name="tiny-moe", arch_type="moe",
        moe={"num_experts": 4, "top_k": 2, "shared_expert_ff": 64})
    r = harness.Run(cell, 7)
    try:
        r.setup()
    finally:
        r.close()
    assert r.cfg.moe == MoEConfig(num_experts=4, top_k=2, shared_expert_ff=64)
    got = r.readings
    assert got["splices"] == cell.traffic["follow"]["splices"]
    assert not got["problems"]
    assert all(0 < loss < 10 for loss in got["losses"])
    assert any("moe" in k for k in got["grad1"])
