"""The program's own spans and counters reach the readers: the harness
copies the executor's ``Profiler`` as the window opens and closes and
hands the difference to ``ctx["program"]``, without resetting or
enabling it."""
import copy

import bench_tiny_cells as tc
from bench import harness, spec

MECHANISM = ["barrier_s", "ckpt_d2h_s", "ckpt_hash_s", "ckpt_new_share",
             "restore_read_s", "resume_build_s", "resize_build_s"]


def _window(cell, seconds):
    """Set-up, one span the program never runs, then the window: what the
    readers get, and the program's Profiler as the window left it."""
    r = harness.Run(cell, 11)
    try:
        r.setup()
        prof = r.ex.prof
        with prof.span("setup.only"):
            pass
        ctx = r.window(seconds)
    finally:
        r.close()
    return ctx, prof


def _read(ctx, name):
    return spec.metric_reader(name)(ctx)


def test_churn_window_reads_the_program():
    cell = tc.tiny("mamba2-130m.churn")
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.traffic["loop"][0]["wait_steps"] = 2      # a preempt early in the window
    ctx, prof = _window(cell, 2.0)
    program = ctx["program"]
    preempts = ctx["events"]["preempt"]
    assert preempts
    assert program["counts"]["ckpt.put"] == len(preempts)
    assert program["counts"]["preempt.barrier"] == len(preempts)
    # the span ran in set-up only; the Profiler kept it (no reset)
    assert program["counts"]["setup.only"] == 0
    assert prof.counts["setup.only"] == 1
    assert not prof.enabled and not prof.annotate
    # the preempt is the barrier steps, the device-to-host copy and the put;
    # the barrier's span opens one call before the preempt's clock starts
    parts = sum(_read(ctx, m) for m in ("barrier_s", "ckpt_d2h_s", "store_put_s"))
    assert parts <= _read(ctx, "preempt_s") + 1e-4
    assert _read(ctx, "ckpt_new_share") == 100.0 * (
        program["counters"]["ckpt.bytes_new"] / program["counters"]["ckpt.bytes"])
    assert 0 < _read(ctx, "ckpt_hash_s") <= _read(ctx, "store_put_s")
    for name in ("step_host_ms", "decide_ms"):
        assert _read(ctx, name) > 0


def test_steady_window_has_no_mechanism():
    ctx, _ = _window(tc.tiny("mamba2-130m.steady"), 0.5)
    for name in MECHANISM:
        assert _read(ctx, name) is None, name
    assert _read(ctx, "step_host_ms") > 0
    assert _read(ctx, "decide_ms") > 0
