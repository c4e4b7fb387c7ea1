"""The controls at a size a test can hold: the float32 reference computed
one precision lower (float8 matmul operands), given half of each batch,
and given half of the batch at the splice-2 steps only, each fails the
committed limits against the float32 reference."""
import pytest

import bench_tiny_cells as tc
from bench import harness
from bench.references.common import Matmul
from bench.traffic import job_seed

SEED = 41


def _follow(cell):
    tr = cell.traffic
    job = next(j for j in tr["jobs"] if j["id"] == tr["follow"]["job"])
    return job_seed(SEED, job["id"]), int(job["total_steps"])


@pytest.mark.parametrize("workload", ["olmo-1b-l4.steady", "mamba2-130m.churn",
                                      "mamba2-130m.steady"])
def test_controls_fail_the_limits(workload):
    cell = tc.tiny(workload)
    seed, total = _follow(cell)
    ref = harness.reference_readings(cell, seed, total)
    half = cell.config["train"]["global_batch"] // 2
    kinds = [{"mm": Matmul("float8_e4m3fn")}, {"rows": half}]
    if 2 in cell.traffic["follow"]["splices"]:
        # a splice-2 step that runs one of its two slices
        kinds.append({"rows": half, "at_splice": 2})
    for kw in kinds:
        got = harness.reference_readings(cell, seed, total, **kw)
        gaps = harness.gaps(got, ref)
        assert any(gaps[k] > cell.limits[k] for k in gaps
                   if k in cell.limits), (kw, gaps)
