"""Loading by name: a cell of ``BENCHMARK.json`` and the files it names.

- configuration ``<name>``: ``bench/configs/<name>.json``; its ``model``
  block is built into ``ModelConfig`` by ``harness.build``, each key whose
  field holds a dataclass (``moe``, ``ssm``, ...) into that dataclass
- traffic mix ``<name>``: ``bench/traffic/<name>.json``
- correctness limits of cell ``<name>``: ``bench/limits/<name>.json``
- metric ``<name>``: ``bench/metrics/<name>.py``, whose ``read(ctx)``
  returns the value or ``None`` when the run has nothing to read.  A
  quantity split by cells, ``<base>.<part>`` (its cells' noise, and so its
  bound, differs), is read by ``bench/metrics/<base>.py`` unless a file of
  its full name exists
- plain reference ``<name>``: ``bench/references/<name>.py``
- FLOP count ``<name>`` (a configuration's ``flops``):
  ``bench/counts/<name>.py``, whose ``per_token(model, seq_len)`` gives
  the model FLOPs of one trained token (``flops.py`` states what counts);
  the same file holds the operation and byte counts of that family's
  kernels
- the program's own spans and counters over the window: ``ctx["program"]``
  of a metric's ``read``, ``{"totals", "counts", "counters"}`` from the
  executor's ``Profiler`` (``harness.program_window``)

A later configuration, cell or metric is added by adding files and
entries; no file here changes for it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> Dict:
    return _json(root / "BENCHMARK.json")


def config(name: str) -> Dict:
    return _json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> Dict:
    return _json(BENCH / "traffic" / f"{name}.json")


def limits(workload: str) -> Dict:
    return _json(BENCH / "limits" / f"{workload}.json")


def _module(path: Path, name: str):
    if not path.is_file():
        raise KeyError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable:
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file() and "." in name:
        name = name.split(".")[0]
        path = BENCH / "metrics" / f"{name}.py"
    return _module(path, f"bench_metric_{name}").read


def reference(name: str):
    return _module(BENCH / "references" / f"{name}.py", f"bench_ref_{name}")


def counts(name: str):
    return _module(BENCH / "counts" / f"{name}.py", f"bench_counts_{name}")


@dataclasses.dataclass
class Cell:
    """One workload with everything it names, loaded."""
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    def metrics(self, trace: bool) -> List[Dict]:
        return self.per_layer if trace else self.end_to_end


def _applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell(workload: str, bench: Optional[Dict] = None) -> Cell:
    bench = bench if bench is not None else benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        names = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"unknown workload {workload!r}; have {names}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cfg = _json(ROOT / cfg_entry["file"])
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config=cfg,
        traffic=traffic(entry["traffic"]),
        limits=limits(workload),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )
