"""The executor's one Profiler inside the mechanisms and the step loop:
a preempt, a restore at splice 2 and a resize to splice 1 leave the
spans and counters they should, nested as they run, and timing them
changes no number the jobs compute."""
import io

import jax
import numpy as np
import pytest

from repro.scheduler.executor import FleetExecutor, ManagedJob
from repro.utils.profiler import Profiler

ARCH = "olmo-1b"
WORLD = 2


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``; keeps the names."""

    def __init__(self):
        self.names = []

    def __call__(self, name):
        self.names.append(name)
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


def _churn(traced: bool, monkeypatch):
    """A basic job of world 2 is preempted by a premium job of world 2,
    restored at splice 2 beside a premium job of world 1, then resized to
    splice 1 once that is done."""
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    ex = FleetExecutor(total_slots=2)
    ex.prof.enabled = ex.prof.annotate = traced
    ex.submit(ManagedJob(id="basic", tier="basic", arch=ARCH,
                         world_size=WORLD, total_steps=100))
    ex.tick()
    ex.tick()
    ex.submit(ManagedJob(id="prem-a", tier="premium", arch=ARCH,
                         world_size=2, total_steps=1))
    ex.tick()
    ex.submit(ManagedJob(id="prem-b", tier="premium", arch=ARCH,
                         world_size=1, total_steps=1))
    ex.tick()
    ex.tick()
    basic = ex.jobs["basic"]
    events = [(e["event"], e["job"]) for e in ex.log]
    assert ("preempt", "basic") in events and ("restore", "basic") in events
    assert ("resize", "basic") in events
    splices = [r["splice"] for r in basic.history]
    assert 2 in splices and splices[-1] == 1
    return ex, ann


@pytest.fixture(scope="module")
def runs():
    with pytest.MonkeyPatch.context() as mp:
        traced = _churn(True, mp)
    with pytest.MonkeyPatch.context() as mp:
        untraced = _churn(False, mp)
    return traced, untraced


def _npy_size(arr) -> int:
    buf = io.BytesIO()
    np.save(buf, arr, allow_pickle=False)
    return len(buf.getvalue())


def test_spans_and_counters_of_a_churn_cycle(runs):
    (ex, ann), _ = runs
    p = ex.prof
    basic = ex.jobs["basic"]
    leaves = len(jax.tree_util.tree_leaves(basic.runtime.state))
    assert p.counts["ckpt.put"] == basic.preemptions == 1
    assert p.counts["preempt.barrier"] == p.counts["ckpt.d2h"] == 1
    assert p.counts["ckpt.serialize"] == p.counts["ckpt.hash"] == leaves * WORLD
    # workers 2..W hold the same state: their copies add no new bytes
    assert 0 < p.counters["ckpt.bytes_new"] <= p.counters["ckpt.bytes"] / WORLD
    # every leaf of the state is hashed straight from its own buffer
    assert p.counters["ckpt.bytes_inplace"] == p.counters["ckpt.bytes"]
    assert p.counts["restore.get"] == p.counts["restore.h2d"] == 1
    assert p.counts["restore.read"] == WORLD
    # the workers' blobs are the same: one worker's copy is built, the
    # others reuse it; so does the optimizer's count, equal to the step
    blobs = {tuple(refs) for worker in ex.store.manifests["basic"][-1][
        "workers"].values() for refs in worker["device"]}
    distinct = sum(len(ex.store._get_chunk(c)) for refs in blobs for c in refs)
    assert p.counters["restore.bytes"] == p.counters["ckpt.bytes"]
    assert p.counters["restore.bytes_built"] == distinct
    assert p.counters["restore.bytes"] / WORLD - distinct == _npy_size(
        np.int32(0))
    assert p.counts["step.build.restore"] == p.counts["step.build.resize"] == 1
    assert p.counts["step.build.admit"] == 3          # basic, prem-a, prem-b
    steps = sum(len(j.history) for j in ex.jobs.values())
    builds = sum(n for k, n in p.counts.items() if k.startswith("step.build."))
    assert p.counts["step.batch"] == p.counts["step.wait"] == steps
    assert p.counts["step.dispatch"] + builds == steps
    assert p.counts["decide"] == 5                    # one per tick
    # one record per span, every one annotated on the trace's clock
    assert len(p.spans) == sum(p.counts.values())
    assert sorted(ann.names) == sorted("repro:" + s[0] for s in p.spans)


def test_spans_nest_as_they_run(runs):
    (ex, _), _ = runs
    recs = ex.prof.spans
    put = [r for r in recs if r[0] == "ckpt.put"]
    hashes = [r for r in recs if r[0] == "ckpt.hash"]
    assert len(put) == 1 and hashes
    for r in hashes:
        assert r[1] == put[0][1] + 1
        assert put[0][4] <= r[4] <= r[5] <= put[0][5]
    barrier = next(r for r in recs if r[0] == "preempt.barrier")
    inside = [r for r in recs if r[0] == "step.wait"
              and barrier[4] <= r[4] <= r[5] <= barrier[5]]
    assert inside and all(r[1] > barrier[1] for r in inside)
    get = next(r for r in recs if r[0] == "restore.get")
    reads = [r for r in recs if r[0] == "restore.read"]
    assert all(r[1] == get[1] + 1 and get[4] <= r[4] <= r[5] <= get[5]
               for r in reads)


def test_timing_changes_no_number(runs):
    (on, _), (off, ann) = runs
    assert off.prof.spans == [] and ann.names == []   # records nothing
    assert off.prof.counts == on.prof.counts          # totals still kept
    for jid, job in on.jobs.items():
        other = off.jobs[jid]
        assert [r["loss"] for r in job.history] == \
            [r["loss"] for r in other.history]
        assert job.steps_done == other.steps_done
    a = jax.tree_util.tree_leaves(on.jobs["basic"].runtime.state)
    b = jax.tree_util.tree_leaves(off.jobs["basic"].runtime.state)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_profiler_counters_and_reset():
    prof = Profiler()
    prof.add("bytes", 3)
    prof.add("bytes", 4)
    with prof.span("outer"):
        pass
    assert prof.counters == {"bytes": 7} and prof.counts == {"outer": 1}
    prof.reset()
    assert prof.counters == {} and prof.counts == {} and prof.totals == {}
