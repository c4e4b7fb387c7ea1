"""The benchmark's cells at a size a CPU test can run: every width shrunk,
the traffic, the limits and the harness as committed.  The model computes
in float32 here, so a sound run agrees with the float32 reference to
rounding and a broken one stands far off."""
import copy
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import harness, spec  # noqa: E402
from repro.core import elastic  # noqa: E402


def tiny(workload: str, dtype: str = "float32") -> spec.Cell:
    cell = spec.cell(workload)
    cfg = copy.deepcopy(cell.config)
    m = cfg["model"]
    m["dtype"] = dtype
    if m["arch_type"] == "dense":
        m.update(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                 d_ff=128, vocab_size=256)
    else:
        m.update(num_layers=2, d_model=64, vocab_size=256)
        m["ssm"].update(state_dim=16, head_dim=16, chunk_size=16)
    cfg["train"].update(global_batch=4, seq_len=64)
    cell.config = cfg
    return cell


def run(cell: spec.Cell, seed: int = 5, seconds: float = 1.0):
    """A whole run without the look for a chip."""
    return harness.run(cell, seed, seconds, False, time.perf_counter(),
                       require_chip=False)


def failed(out) -> set:
    return {k for k, c in out["checks"].items() if not c["value"] <= c["limit"]}


def break_step(monkeypatch, fault: str) -> None:
    """Every step program the runtimes build gets ``fault``:
    ``half_batch`` runs the first half of the batch's rows, its mean over
    those; ``unchanged`` returns parameters and optimizer state as they went
    in."""
    orig = elastic.build_train_step

    def build(cfg, tcfg, splice=1, **kw):
        step = orig(cfg, tcfg, splice=splice, **kw)

        def broken(state, batch, flags=None):
            if fault == "half_batch":
                half = jax.tree_util.tree_map(lambda a: a[: a.shape[0] // 2], batch)
                return step(state, half, flags)
            new, metrics = step(state, batch, flags)
            return {"params": state["params"], "opt": state["opt"],
                    "step": new["step"]}, metrics
        return broken

    monkeypatch.setattr(elastic, "build_train_step", build)
