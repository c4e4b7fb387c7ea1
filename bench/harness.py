"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and the result line.

Set-up builds a ``FleetExecutor``, submits the cell's traffic, and ticks
it until the followed job has run the steps the reference follows and the
traffic's warm-up loops are done.  Those ticks go through the window's own
``FleetExecutor.tick``, compile every program the window runs (each step
program, the checkpoint and restore path) and are what the reference is
compared with.  The window then ticks the same executor for ``seconds``.
Nothing is compared inside the window but the restores' fingerprints;
the reference runs after the window, once the program's state is freed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import time
import typing
from typing import Dict, List, Optional

import numpy as np

from bench import flops, peaks, spec as specs
from bench import trace as trace_mod
from bench.probes import Probes
from bench.references.common import Matmul, leaf_norms, train_readings
from bench.traffic import Driver

STEP_PROGRAM = "jit_train_step"
TRAIN_KEYS = ("learning_rate", "warmup_steps", "weight_decay", "beta1",
              "beta2", "eps", "grad_clip", "remat", "remat_policy",
              "zero_shard_factor")
ZERO_GRAD = 1e-3      # a leaf whose reference gradient is under this share
                      # of the median leaf's is moved by round-off alone


class NoChip(RuntimeError):
    pass


def _frozen(value):
    """JSON lists as tuples, at any depth, so that the value hashes."""
    if isinstance(value, list):
        return tuple(_frozen(v) for v in value)
    return value


def _dataclass_of(hint):
    """The dataclass a field holds, through ``Optional``; else ``None``."""
    if dataclasses.is_dataclass(hint):
        return hint
    args = [a for a in typing.get_args(hint) if a is not type(None)]
    if typing.get_origin(hint) is typing.Union and len(args) == 1:
        return _dataclass_of(args[0])
    return None


def build(cls, block: Dict):
    """Dataclass ``cls`` from a JSON object: a key whose field holds a
    dataclass (``ModelConfig.moe``, ``.ssm``, ...) is built into it, lists
    become tuples, and a key ``cls`` has no field for raises."""
    hints = typing.get_type_hints(cls)
    names = {f.name for f in dataclasses.fields(cls)}
    unknown = set(block) - names
    if unknown:
        raise TypeError(f"{cls.__name__} has no field {sorted(unknown)}")
    fields = {}
    for key, value in block.items():
        sub = _dataclass_of(hints[key])
        fields[key] = (build(sub, value) if sub is not None
                       and isinstance(value, dict) else _frozen(value))
    return cls(**fields)


def model_config(config: Dict):
    from repro.configs.base import ModelConfig
    return build(ModelConfig, config["model"])


def check_train_config(tcfg, train: Dict) -> None:
    """The program must run the optimizer the configuration states."""
    off = {k: (getattr(tcfg, k), train[k]) for k in TRAIN_KEYS
           if getattr(tcfg, k) != train[k]}
    if off:
        raise ValueError(f"the program's training config departs from the "
                         f"configuration file: {off}")


def batch(seed: int, step: int, rows: int, seq_len: int, vocab: int):
    """Tokens and labels of one global batch: row r of step ``step`` is the
    counter-mode Philox stream ``(key=seed, counter=[0, 0, step, r])``, the
    recipe the job's data pipeline states."""
    out = np.empty((rows, seq_len + 1), np.int32)
    for r in range(rows):
        rng = np.random.Generator(np.random.Philox(key=seed,
                                                   counter=[0, 0, step, r]))
        out[r] = rng.integers(0, vocab, seq_len + 1, dtype=np.int32)
    return out[:, :-1], out[:, 1:]


def _host_flat(tree) -> Dict[str, np.ndarray]:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(x) for p, x in flat}


def change_norm(now: np.ndarray, before: np.ndarray,
                chunk: int = 1 << 22) -> float:
    """Norm of ``now - before`` in float64, a few million words at a time,
    so that no temporary is the size of the leaf."""
    a, b = now.reshape(-1), before.reshape(-1)
    total = 0.0
    for i in range(0, a.size, chunk):
        d = a[i:i + chunk].astype(np.float64) - b[i:i + chunk]
        total += float(np.dot(d, d))
    return math.sqrt(total)


class Follower:
    """Readings of the followed job during set-up: every step's loss,
    splice and global gradient norm, each leaf's norm of the first step's
    gradient as the optimizer got it (its first moment after one step over
    ``1 - beta1``), and each leaf's norm of the parameters' change over the
    followed steps."""

    def __init__(self, job, splices: List[int], grad_norms: List):
        import jax
        self.job = job
        self.grad_norms = grad_norms    # the probes' list for this job
        self.splices = list(splices)
        self.beta1 = job.train_config().beta1
        self.p0 = _host_flat(job.runtime.state["params"])
        self.grad1: Optional[Dict[str, float]] = None
        self.change: Optional[Dict[str, float]] = None
        self.problems: List[str] = []
        self._norms = jax.jit(leaf_norms)

    @property
    def done(self) -> bool:
        return self.change is not None

    def after_tick(self) -> None:
        n, rt = self.job.steps_done, self.job.runtime
        if self.grad1 is None and n >= 1:
            if n == 1 and rt is not None:
                norms = self._norms(rt.state["opt"]["m"])
                self.grad1 = {k: float(v) / (1.0 - self.beta1)
                              for k, v in norms.items()}
            else:
                self.problems.append(f"step 1 not readable (at step {n})")
                self.grad1 = {}
        if self.change is None and n >= len(self.splices):
            if n == len(self.splices) and rt is not None:
                now = _host_flat(rt.state["params"])
                self.change = {k: change_norm(now[k], p0)
                               for k, p0 in self.p0.items()}
            else:
                self.problems.append(
                    f"step {len(self.splices)} not readable (at step {n})")
                self.change = {}
            self.p0 = None

    def readings(self) -> Dict:
        first, norms = {}, {}
        history = self.job.history
        if len(self.grad_norms) != len(history):
            self.problems.append(f"{len(self.grad_norms)} step calls for "
                                 f"{len(history)} step records")
        for r, g in zip(history, self.grad_norms):
            norms.setdefault(r["step"], float(g))
        for r in history:
            first.setdefault(r["step"], r)
        steps = range(1, len(self.splices) + 1)
        return {
            "losses": [first[s]["loss"] if s in first else math.nan
                       for s in steps],
            "grad_norms": [norms.get(s, math.nan) for s in steps],
            "splices": [first[s]["splice"] if s in first else 0
                        for s in steps],
            "history_steps": [r["step"] for r in self.job.history
                              if r["step"] <= len(self.splices)],
            "grad1": self.grad1 or {},
            "change": self.change or {},
            "problems": list(self.problems),
        }


def _rel(a: float, b: float, scale: float) -> float:
    return abs(a - b) / scale if scale > 0 else math.inf


def leaf_gaps(got: Dict[str, float], want: Dict[str, float],
              keep=None) -> Dict[str, float]:
    """Per leaf, |norm(program) - norm(reference)| over the larger of the
    reference's norm of that leaf and the median leaf's."""
    keys = [k for k in want if keep is None or k in keep]
    if not keys:
        return {"(no leaf)": math.inf}
    median = float(np.median([want[k] for k in keys]))
    return {k: (_rel(got[k], want[k], max(want[k], median)) if k in got
                else math.inf) for k in keys}


def leaf_gap(got: Dict[str, float], want: Dict[str, float],
             keep=None) -> float:
    """The worst leaf of ``leaf_gaps``."""
    return max(leaf_gaps(got, want, keep).values())


def moved_leaves(grad1: Dict[str, float]) -> set:
    """Leaves whose reference gradient is not nought to rounding."""
    median = float(np.median(list(grad1.values())))
    return {k for k, v in grad1.items() if v >= ZERO_GRAD * median}


def _worst_step(got: List[float], want: List[float]) -> float:
    return max((abs(a - b) / abs(b) if math.isfinite(a) else math.inf)
               for a, b in zip(got, want))


def gaps(readings: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers compared between a run's readings and a reference's."""
    return {
        "loss_gap": _worst_step(readings["losses"], ref["losses"]),
        "gnorm_gap": _worst_step(readings["grad_norms"], ref["grad_norms"]),
        "grad_gap": leaf_gap(readings["grad1"], ref["grad1"]),
        "change_gap": leaf_gap(readings["change"], ref["change"],
                               moved_leaves(ref["grad1"])),
    }


def compare(readings: Dict, ref: Dict, limits: Dict,
            restore_checks: List[int]) -> List[Dict]:
    """Every compared number beside its limit, in a fixed order."""
    values = gaps(readings, ref)
    want = list(range(1, len(readings["splices"]) + 1))
    planned = ref["splices"]
    values["trajectory_mismatch"] = (
        sum(a != b for a, b in zip(readings["splices"], planned))
        + (readings["history_steps"] != want) + len(readings["problems"]))
    if "restore_mismatch" in limits:
        # -1: a restore with no checkpoint fingerprint to compare with;
        # none at all: the path the limit is for never ran
        values["restore_mismatch"] = (
            sum(1 if c < 0 else c for c in restore_checks)
            + (0 if restore_checks else 1))
    return [{"name": k, "value": values[k], "limit": limits[k],
             "ok": bool(values[k] <= limits[k])} for k in limits]


def reference_readings(cell: specs.Cell, follow_seed: int, total_steps: int,
                       mm: Optional[Matmul] = None, rows: Optional[int] = None,
                       at_splice: Optional[int] = None) -> Dict:
    """The plain reference over the followed steps.  ``rows`` keeps only the
    first rows of each batch, or with ``at_splice`` only of the batches of
    the steps planned at that splice (planted faults: the mean over the
    rows kept)."""
    cfg = cell.config
    train = cfg["train"]
    ref = specs.reference(cfg["reference"])
    splices = cell.traffic["follow"]["splices"]
    batches = []
    for step, splice in enumerate(splices):
        t, lab = batch(follow_seed, step, train["global_batch"],
                       train["seq_len"], cfg["model"]["vocab_size"])
        cut = rows and at_splice in (None, splice)
        batches.append((t[:rows], lab[:rows]) if cut else (t, lab))
    out = train_readings(ref, cfg["model"], train, total_steps, follow_seed,
                         batches, mm or Matmul())
    out["splices"] = splices
    return out


class Compiles:
    """Counts programs JAX built (compiled, or loaded from the persistent
    cache) and the compiles among them, from JAX's monitoring events."""

    LOAD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.loads = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._load)
        jax.monitoring.register_event_listener(self._hit)

    def _load(self, event, duration, **_):
        self.loads += event == self.LOAD

    def _hit(self, event, **_):
        self.hits += event == self.HIT

    def counts(self):
        return self.loads, self.loads - self.hits

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._load)
        jax.monitoring.unregister_event_listener(self._hit)


def program_totals(prof) -> Dict[str, Dict]:
    """A copy of what the program's own ``Profiler`` has accumulated: per
    span its seconds and calls, and its counters.  It is read, never reset
    or switched on, so the program runs as it does without the benchmark."""
    return {"totals": dict(prof.totals), "counts": dict(prof.counts),
            "counters": dict(prof.counters)}


def program_window(before: Dict, after: Dict) -> Dict[str, Dict]:
    """What the program's spans and counters added between two copies; a
    span that ran only before the first copy reads 0 calls."""
    return {part: {k: v - before[part].get(k, 0) for k, v in after[part].items()}
            for part in after}


class Run:
    """Set-up state of one run: the executor, its traffic driver, the
    probes and the followed job's readings."""

    def __init__(self, cell: specs.Cell, seed: int):
        self.cell = cell
        self.seed = seed
        train = cell.config["train"]
        self.cfg = model_config(cell.config)
        self.gb, self.sl = int(train["global_batch"]), int(train["seq_len"])
        self.probes = Probes()
        self.compiles = Compiles()

    def setup(self) -> None:
        from repro.scheduler.executor import FleetExecutor
        tr = self.cell.traffic
        t0 = time.perf_counter()
        self.probes.install()
        self.ex = FleetExecutor(total_slots=int(tr["slots"]))
        self.drv = Driver(self.ex, tr, self.seed, self.cfg, self.gb, self.sl)
        self.drv.start()
        follow = tr["follow"]
        job = self.ex.jobs[follow["job"]]
        self.follow_seed = job.seed
        self.follow_total_steps = job.total_steps
        check_train_config(job.train_config(), self.cell.config["train"])
        t1 = time.perf_counter()
        self.follower = Follower(job, follow["splices"],
                                 self.probes.grad_norms[job.seed])
        t2 = time.perf_counter()
        cap = 20 * len(follow["splices"]) + 50
        for _ in range(cap):
            if self.follower.done and self.drv.loops >= int(tr["warmup_loops"]):
                break
            self.drv.tick()
            self.follower.after_tick()
        self.readings = self.follower.readings()
        # where set-up's time went: the jobs' state made, the followed
        # job's parameters copied to the host, set-up's ticks
        self.phases = {"submit": t1 - t0, "follow": t2 - t1,
                       "ticks": time.perf_counter() - t2}
        self.setup_loaded, self.setup_compiles = self.compiles.counts()
        if not self.follower.done:
            self.readings["problems"].append("set-up never reached the "
                                             "followed steps")

    def window(self, seconds: float, trace_dir: Optional[str] = None) -> Dict:
        """Tick for ``seconds``; what the window did, on the host clock."""
        import jax
        history0 = {jid: len(j.history) for jid, j in self.ex.jobs.items()}
        self.probes.reset()
        c0 = self.compiles.counts()
        prog0 = program_totals(self.ex.prof)
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            self.probes.annotate = True
        span = (jax.profiler.TraceAnnotation(trace_mod.WINDOW) if trace_dir
                else contextlib.nullcontext())
        t0 = time.perf_counter()
        with span:
            while time.perf_counter() - t0 < seconds:
                self.drv.tick()
        t1 = time.perf_counter()
        program = program_window(prog0, program_totals(self.ex.prof))
        if trace_dir:
            jax.profiler.stop_trace()
            self.probes.annotate = False
        loaded, compiled = (b - a for a, b in zip(c0, self.compiles.counts()))
        steps = bad = tokens = 0
        for jid, j in self.ex.jobs.items():
            new = j.history[history0.get(jid, 0):]
            steps += len(new)
            bad += sum(not math.isfinite(r["loss"]) for r in new)
            tokens += len(new) * self.gb * self.sl
        return {"window_s": t1 - t0, "steps": steps, "failed": bad,
                "tokens": tokens, "programs_loaded": loaded,
                "compiles_in_window": compiled, "program": program,
                "events": {k: list(v) for k, v in self.probes.events.items()},
                "event_ends": {k: [t - t0 for t in v] for k, v in
                               self.probes.event_ends.items()},
                "spans": {k: self.probes.durations(k)
                          for k in list(self.probes.spans)}}

    def close(self) -> None:
        """Free the program's state; the probes come off."""
        self.probes.uninstall()
        self.compiles.close()
        self.restore_checks = self.probes.restore_mismatches()
        for name in ("drv", "ex", "follower"):
            self.__dict__.pop(name, None)
        self.probes.forget_state()
        gc.collect()


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def device_info() -> Dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run(cell: specs.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True,
        trace_dir: Optional[str] = None) -> Dict:
    """One whole run; returns the result line's object."""
    info = device_info()
    if require_chip and (info["platform"] != "tpu" or info["count"] < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
                     f"found {info['count']} {info['platform']} device(s)")
    r = Run(cell, seed)
    tmp = None
    t_setup = time.perf_counter()
    try:
        r.setup()
        setup_s = time.perf_counter() - t_start
        if trace:
            tmp = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        w = r.window(seconds, tmp)
    finally:
        r.close()
    info["memory_peak_bytes"] = memory_peak_bytes()
    reduced = None
    if trace:
        reduced = trace_mod.reduce(trace_mod.load(trace_mod.find_xplane(tmp)))
        if trace_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
        info["busy_s"] = reduced.busy_s
        info["window_s"] = reduced.window_s
    ref = reference_readings(cell, r.follow_seed, r.follow_total_steps)
    checks = compare(r.readings, ref, cell.limits, r.restore_checks)
    ctx = {
        "cell": cell, "setup_s": setup_s, "trace": reduced,
        "flops_per_token": flops.per_token(cell.config),
        "peak": peaks.peak(info["kind"]) if require_chip else None,
        "step_program": STEP_PROGRAM, **w,
    }
    metrics = {}
    for m in cell.metrics(trace):
        value = specs.metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out = {
        "correct": all(c["ok"] for c in checks),
        "attempted": w["steps"],
        "failed": w["failed"],
        "metrics": metrics,
        "device": info,
    }
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced.top_ops(),
                            "idle_gaps": reduced.idle_gaps()}
    out["info"] = {"seed": seed, "setup_s": setup_s, "window_s": w["window_s"],
                   "steps": w["steps"],
                   "setup_phases": {"start": t_setup - t_start, **r.phases},
                   "setup_programs": [r.setup_loaded, r.setup_compiles],
                   "compiles_in_window": w["compiles_in_window"],
                   "programs_loaded": w["programs_loaded"],
                   "events": w["events"],
                   "event_ends": w["event_ends"],
                   "step_s_median": (float(np.median(w["spans"]["step"]))
                                     if w["spans"].get("step") else None),
                   # where a slow run's time went: the window's steps at
                   # their 0th, 50th, 90th and 100th percentile, and the
                   # host's share of a step (its program call's dispatch)
                   "step_s_percentiles": _percentiles(w["spans"].get("step")),
                   "dispatch_s_percentiles": _percentiles(
                       w["spans"].get("dispatch")),
                   "spans": {k: [len(v), sum(v)] for k, v in w["spans"].items()},
                   "program": {
                       "spans": {k: [n, w["program"]["totals"][k]] for k, n in
                                 w["program"]["counts"].items() if n},
                       "counters": w["program"]["counters"]},
                   "gaps": gaps(r.readings, ref),
                   "losses": r.readings["losses"],
                   "reference_losses": ref["losses"]}
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    return out


def _percentiles(values) -> Optional[List[float]]:
    if not values:
        return None
    return [float(v) for v in np.percentile(values, [0, 50, 90, 100])]


def _finite(x):
    """JSON has no infinity: a number that could not be read prints as
    1e300, which fails every limit."""
    if isinstance(x, float) and not math.isfinite(x):
        return 1e300
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    return x


def print_result(out: Dict, stream=sys.stdout) -> None:
    """The checks as the last lines of standard error, then the result as
    the last line of standard output."""
    for name, c in out["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(_finite(out)), file=stream, flush=True)
