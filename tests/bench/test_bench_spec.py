"""The benchmark's layout: what BENCHMARK.json names exists and loads by
name, the traffic is fixed by the seed, and a run without a chip prints
no result."""
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from bench.traffic import Driver, job_seed  # noqa: E402

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= {w["name"] for w in BENCH["workloads"]}
    assert (ROOT / "BENCHMARK.json").stat().st_size < 64 * 1024


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_by_name(workload):
    cell = spec.cell(workload)
    entry = next(w for w in BENCH["workloads"] if w["name"] == workload)
    assert cell.chips == entry["chips"] in (1, 4)
    assert cell.config == spec.config(entry["config"])
    assert cell.traffic == spec.traffic(entry["traffic"])
    assert set(cell.limits) >= {"grad_gap", "change_gap",
                                "trajectory_mismatch"}
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.metrics(trace=True), "every cell reports a per-layer metric"
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cuts(entry):
    cfg = spec.config(entry["name"])
    assert ROOT / entry["file"] == spec.BENCH / "configs" / f"{entry['name']}.json"
    assert cfg["reduced"] == entry["reduced"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    for key in cfg["reduced"]:
        stated = cfg["model"][key] if key in cfg["model"] else cfg[key]
        assert stated != cfg["published"][key]
    ref = spec.reference(cfg["reference"])
    assert ref.loss_sum
    if "norm_epsilon" in cfg:
        assert ref.EPS == cfg["norm_epsilon"]


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        spec.cell("no-such-cell")
    with pytest.raises(KeyError):
        spec.metric_reader("no_such_metric")


class _Ex:
    """Stands in for the executor: records what is submitted."""

    def __init__(self):
        self.jobs = {}

    def submit(self, job, *_a, **_k):
        self.jobs[job.id] = job


class _Cfg:
    name = "mamba2-130m"


def test_churn_traffic_fixed_by_seed():
    tr = spec.traffic("churn")
    seed = 3_000_000_017       # beyond 32 bits, as a run's --seed may be
    a = Driver(_Ex(), tr, seed, _Cfg(), 18, 2048).schedule(4)
    b = Driver(_Ex(), tr, seed, _Cfg(), 18, 2048).schedule(4)
    c = Driver(_Ex(), tr, seed + 2 ** 32, _Cfg(), 18, 2048).schedule(4)
    assert a == b
    strip = [{k: v for k, v in j.items() if k != "seed"} for j in a]
    assert strip == [{k: v for k, v in j.items() if k != "seed"} for j in c]
    assert [j["seed"] for j in a] != [j["seed"] for j in c]
    assert all(0 <= j["seed"] < 2 ** 31 for j in a)
    assert len({j["seed"] for j in a}) == len(a)
    assert [j["world"] for j in a] == [2] + [2, 1] * 4


def test_loop_submits_in_order():
    """The closed loop: an arrival waits for the steps, then for the done."""
    tr = spec.traffic("churn")
    ex = _Ex()
    d = Driver(ex, tr, 7, _Cfg(), 18, 2048)
    d.start()
    basic = ex.jobs["basic"]
    basic.steps_done, basic.done = 0, False
    d.arrivals()
    assert list(ex.jobs) == ["basic"]
    basic.steps_done = 2
    d.arrivals()
    assert list(ex.jobs) == ["basic", "premium-a-0"]
    ex.jobs["premium-a-0"].done = True
    d.arrivals()
    assert list(ex.jobs) == ["basic", "premium-a-0", "premium-b-0"]
    ex.jobs["premium-b-0"].done = True
    d.arrivals()
    assert d.loops == 1 and d.mark == 2
    # past set-up's loop, the window's loop waits its own K steps
    k = tr["loop"][0]["wait_steps"]
    assert k != tr["warmup_loop"][0]["wait_steps"]
    basic.steps_done = 2 + k - 1
    d.arrivals()
    assert "premium-a-1" not in ex.jobs
    basic.steps_done = 2 + k
    d.arrivals()
    assert list(ex.jobs)[-1] == "premium-a-1"


def test_job_seed_large_seeds():
    assert job_seed(2 ** 31 + 5, "basic") != job_seed(5, "basic")
    assert job_seed(2 ** 33 + 5, "basic") != job_seed(5, "basic")
    assert job_seed(5, "basic") == job_seed(5, "basic")


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "olmo-1b-l4.steady",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "needs 1 TPU" in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    """Without the program beside it the benchmark fails, printing nothing."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
