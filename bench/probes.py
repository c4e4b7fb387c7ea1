"""Spans and event timers around the program's entry points.

The benchmark times the program from its own process: ``Probes.install``
wraps the named entry points at class or module level and ``uninstall``
puts them back; nothing in ``src/`` changes.  A renamed entry point makes
``install`` raise, so the metric that reads it goes missing loudly.

Host-clock spans (``time.perf_counter``), kept in memory:

- ``submit``  ``FleetExecutor.submit`` (a job's state is made here)
- ``decide``  ``FleetExecutor._decide_allocations``
- ``apply``   ``FleetExecutor._apply``
- ``batch``   ``ElasticRuntime._batch``
- ``step``    ``ElasticRuntime.run_steps``
- ``dispatch`` one call of the step program, until it returns (enqueued,
  not finished)
- ``checkpoint`` ``checkpoint_job`` as the executor calls it
- ``store_put`` ``CheckpointStore.snapshot``
- ``store_get`` ``CheckpointStore.restore``

Mechanism events, each one duration in ``events``:

- ``preempt``: from ``ElasticRuntime.request_preemption`` until
  ``checkpoint_job`` returns (barrier steps, device->host copy, serialize,
  hash and dedup);
- ``resume``: from entry to ``CheckpointStore.restore`` until the first
  ``run_steps`` of the restored runtime returns (store read, host->device,
  step program, first step);
- ``resize``: from entry to ``ElasticRuntime.resize`` until the next
  ``run_steps`` of that runtime returns.

``run_steps`` reads each step's loss back to the host, so its return
means the step has finished on the device.

``grad_norms`` keeps, per job seed, the global gradient norm that every
step program call returns, as device scalars in call order: one per
record a job's ``history`` gets.

The state a checkpoint is taken of and the state a restore builds are each
fingerprinted on the device.  The probes add no wait for the device to any
event: the checkpoint's fingerprint is dispatched once the preempt's time
is taken, and the restore's comparison stays a device array until
``restore_mismatches`` reads it after the window.  Only the restore's
fingerprint runs inside ``resume``, on the device, before the first step
(which consumes the restored state); it reads the state once.  With
``annotate`` on, every span is also a ``jax.profiler.TraceAnnotation``
named ``bench:<span>``, so a device trace can tell what the host was doing.
"""
from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import checkpoint as ckpt_mod
from repro.core import elastic as elastic_mod
from repro.scheduler import executor as executor_mod

ANNOTATION_PREFIX = "bench:"


def _leaf_fingerprint(x: jax.Array) -> jax.Array:
    """int32 digest of one leaf: sum of its 32-bit words times odd weights,
    wrapping; any single changed word changes it."""
    if x.dtype.itemsize == 4:
        w = jax.lax.bitcast_convert_type(x, jnp.int32)
    else:
        w = x.astype(jnp.int32)
    w = w.reshape(-1)
    weights = jnp.arange(w.size, dtype=jnp.int32) * jnp.int32(-1640531535) | 1
    return jnp.sum(w * weights, dtype=jnp.int32)


@jax.jit
def fingerprint(tree) -> jax.Array:
    return jnp.stack([_leaf_fingerprint(x) for x in jax.tree_util.tree_leaves(tree)])


class Probes:
    def __init__(self):
        self.annotate = False
        self.spans: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
        self.events: Dict[str, List[float]] = defaultdict(list)
        self.event_ends: Dict[str, List[float]] = defaultdict(list)
        self.grad_norms: Dict[int, List[jax.Array]] = defaultdict(list)
        # per restore: count of differing leaves (a device array), or None
        # where no checkpoint of that job was fingerprinted
        self._restore_diffs: List[Optional[jax.Array]] = []
        self._ckpt_fp: Dict[str, jax.Array] = {}
        self._preempt_t0: Dict[int, float] = {}
        self._restore: Tuple[str, float] | None = None
        self._saved: List[Tuple[object, str, object]] = []

    def reset(self) -> None:
        """Forget spans, events and gradient norms (the window starts);
        restore checks stay."""
        self.spans.clear()
        self.events.clear()
        self.event_ends.clear()
        self.grad_norms.clear()

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name):
                yield
        else:
            yield
        self.spans[name].append((t0, time.perf_counter()))

    def event(self, kind: str, t0: float) -> None:
        t1 = time.perf_counter()
        self.events[kind].append(t1 - t0)
        self.event_ends[kind].append(t1)

    # ------------------------------------------------------------ install
    def _patch(self, owner, attr: str, make) -> None:
        if attr not in vars(owner):
            raise AttributeError(f"{owner.__name__}.{attr} is gone: "
                                 "the benchmark's probe needs repair")
        orig = vars(owner)[attr]
        self._saved.append((owner, attr, orig))
        setattr(owner, attr, make(orig))

    def _spanned(self, name: str):
        def make(orig):
            def wrapped(*a, **k):
                with self.span(name):
                    return orig(*a, **k)
            return wrapped
        return make

    def install(self) -> "Probes":
        ER = elastic_mod.ElasticRuntime
        FE = executor_mod.FleetExecutor
        CS = ckpt_mod.CheckpointStore
        self._patch(FE, "submit", self._spanned("submit"))
        self._patch(FE, "_decide_allocations", self._spanned("decide"))
        self._patch(FE, "_apply", self._spanned("apply"))
        self._patch(ER, "_batch", self._spanned("batch"))
        self._patch(CS, "snapshot", self._spanned("store_put"))
        probes = self

        def run_steps(orig):
            def wrapped(rt, *a, **k):
                with probes.span("step"):
                    out = orig(rt, *a, **k)
                pending = rt.__dict__.pop("_bench_pending", None)
                if pending is not None:
                    kind, t0 = pending
                    probes.event(kind, t0)
                return out
            return wrapped

        def step_fn(orig):
            def wrapped(rt):
                fn = orig(rt)
                norms = probes.grad_norms[rt.tcfg.seed]

                def recorded(state, batch, flags=None):
                    with probes.span("dispatch"):
                        new, metrics = fn(state, batch, flags)
                    norms.append(metrics["grad_norm"])
                    return new, metrics
                return recorded
            return wrapped

        def request_preemption(orig):
            def wrapped(rt):
                probes._preempt_t0[id(rt)] = time.perf_counter()
                return orig(rt)
            return wrapped

        def resize(orig):
            def wrapped(rt, new_physical):
                t0 = time.perf_counter()
                out = orig(rt, new_physical)
                rt._bench_pending = ("resize", t0)
                return out
            return wrapped

        def checkpoint_job(orig):
            def wrapped(runtime, store, job_id):
                t0 = probes._preempt_t0.pop(id(runtime), None)
                with probes.span("checkpoint"):
                    out = orig(runtime, store, job_id)
                if t0 is not None:
                    probes.event("preempt", t0)
                # the state just checkpointed, still held by the runtime
                probes._ckpt_fp[job_id] = fingerprint(runtime.state)
                return out
            return wrapped

        def restore(orig):
            def wrapped(store, job_id, *a, **k):
                t0 = time.perf_counter()
                with probes.span("store_get"):
                    out = orig(store, job_id, *a, **k)
                probes._restore = (job_id, t0)
                return out
            return wrapped

        def from_snapshot(orig):
            func = orig.__func__

            def wrapped(cls, *a, **k):
                rt = func(cls, *a, **k)
                if probes._restore is not None:
                    job_id, t0 = probes._restore
                    probes._restore = None
                    rt._bench_pending = ("resume", t0)
                    want = probes._ckpt_fp.get(job_id)
                    probes._restore_diffs.append(
                        None if want is None else
                        jnp.sum(fingerprint(rt.state) != want))
                return rt
            return classmethod(wrapped)

        self._patch(ER, "run_steps", run_steps)
        self._patch(ER, "_step_fn", step_fn)
        self._patch(ER, "request_preemption", request_preemption)
        self._patch(ER, "resize", resize)
        self._patch(ER, "from_snapshot", from_snapshot)
        self._patch(CS, "restore", restore)
        self._patch(executor_mod, "checkpoint_job", checkpoint_job)
        return self

    def uninstall(self) -> None:
        while self._saved:
            owner, attr, orig = self._saved.pop()
            setattr(owner, attr, orig)

    # ------------------------------------------------------------ reads
    def restore_mismatches(self) -> List[int]:
        """Per restore so far, how many state leaves differ from the
        checkpoint's; -1 where there was no checkpoint fingerprint."""
        return [-1 if d is None else int(d) for d in self._restore_diffs]

    def forget_state(self) -> None:
        """Drop every device array the probes hold."""
        self._ckpt_fp.clear()
        self._restore_diffs = []
        self.grad_norms.clear()

    def durations(self, name: str) -> List[float]:
        return [t1 - t0 for t0, t1 in self.spans.get(name, [])]
