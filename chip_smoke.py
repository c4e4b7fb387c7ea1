"""Chip smoke run: the elastic executor's main path on a TPU, at olmo-1b width.

    python chip_smoke.py            # one chip: reference + executor phases
    python chip_smoke.py --chips 4  # four chips: the sharded (2, 2) step only

It runs in one process and starts no child: a TPU belongs to one process.
It fails (non-zero exit, no result line) when JAX finds no TPU; there is
no CPU fallback.  The last line of stdout is the JSON result,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
after every phase and check passed.

One chip:

1. Reference: one ``ElasticRuntime`` of the olmo job runs ``OLMO_STEPS``
   steps uninterrupted at splice 1; its losses are kept and it is dropped.
2. Executor: ``FleetExecutor(total_slots=2)`` runs the same basic-tier
   olmo job (world size 2, so splice 1 on two slots).  A premium
   mamba2-130m job (world 2) arrives and the policy preempts olmo through
   the in-graph barrier and the deduplicated checkpoint.  Once it is
   done, a second premium mamba2-130m job (world 1) arrives: olmo is
   restored on the one free slot (splice 2) and resized back to splice 1
   when that job is done.
3. Checks: the log holds a preempt, a restore at the checkpointed step
   and a resize; the restored state equals the checkpointed one bit for
   bit; every loss is finite; the olmo losses match the reference at the
   same steps within ``LOSS_RTOL``.

Four chips: a (2, 2) ("data", "model") mesh with Auto axes; the olmo state
and batch placed by ``param_specs`` / ``batch_specs``; ``SHARDED_STEPS``
barrier-carrying steps, with a preemption requested so that
``BarrierDriver(n_shards=2)`` acquires the barrier through the step's psum
over the data axis; losses against a one-chip reference on device 0.

Cuts (weights random from seed 0, tokens synthetic from seed 0):

- olmo-1b keeps its published widths (d_model 2048, 16 heads, d_ff 8192,
  vocab 50304); depth is cut to 4 of its 16 layers.  Its fp32 parameters
  and two AdamW moments take 12 B per parameter: the whole model's ~14 GB
  of state cannot train on one 16 GiB chip, 4 layers take 4.15 GiB.
- olmo global batch 4 x seq 2048: the v5e compile of the step puts
  arguments + outputs + temporaries at 11.8 GiB at splice 1.
- mamba2-130m runs at its published size (24 layers, d_model 768), global
  batch 4 x seq 2048, ``PREMIUM_STEPS`` steps per premium job.
- ``OLMO_STEPS`` olmo steps; the four-chip path runs ``SHARDED_STEPS``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core.barrier_jax import BarrierDriver
from repro.core.elastic import ElasticRuntime
from repro.data.pipeline import DataPipeline
from repro.launch.mesh import make_mesh
from repro.parallel.sharding import batch_specs, param_specs, to_shardings
from repro.scheduler.executor import FleetExecutor, ManagedJob
from repro.training.state import init_train_state
from repro.training.step import build_train_step
from repro.utils.compile_cache import enable_compile_cache

OLMO_LAYERS = 4
GLOBAL_BATCH, SEQ_LEN = 4, 2048
OLMO_STEPS = 10
PREMIUM_STEPS = 2
SHARDED_STEPS = 3
# Relative loss tolerance against the reference.  Compute is bf16 (8
# significant bits, rounding up to 2**-8 = 3.9e-3 relative): a splice-2
# step sums two half-batch bf16 gradients in fp32 where splice 1 rounds one
# full-batch gradient, and a sharded step reduces partial sums in another
# order, so gradients differ by about one bf16 rounding and AdamW carries
# that into later losses.  1e-2 is ~2.5 roundings; a wrong restore, a lost
# or repeated step, or a wrong shard moves the loss by far more.
LOSS_RTOL = 1e-2
GiB = 1 << 30


def olmo_config() -> ModelConfig:
    return dataclasses.replace(get_config("olmo-1b"), num_layers=OLMO_LAYERS)


def say(msg: str) -> None:
    print(msg, flush=True)


def peak_gib(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / GiB:.3f} GiB"


def check_losses(name: str, got: list, ref: list) -> float:
    """Both finite and equal within LOSS_RTOL; returns the worst rel diff."""
    assert len(got) == len(ref), (name, len(got), len(ref))
    worst = 0.0
    for step, (a, b) in enumerate(zip(got, ref), start=1):
        assert math.isfinite(a) and math.isfinite(b), (name, step, a, b)
        rel = abs(a - b) / abs(b)
        worst = max(worst, rel)
        assert rel <= LOSS_RTOL, \
            f"{name}: step {step} loss {a!r} vs reference {b!r} (rel {rel:.3g})"
    return worst


def host_tree(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def assert_bit_equal(got, want) -> None:
    g, gdef = jax.tree_util.tree_flatten(host_tree(got))
    w, wdef = jax.tree_util.tree_flatten(want)
    assert gdef == wdef, (gdef, wdef)
    for a, b in zip(g, w):
        assert a.dtype == b.dtype and a.shape == b.shape, (a.dtype, b.dtype)
        assert a.tobytes() == b.tobytes(), "restored state differs from checkpoint"


# ------------------------------------------------------------- one chip
def run_one_chip(olmo: ModelConfig, premium: ModelConfig,
                 global_batch: int = GLOBAL_BATCH, seq_len: int = SEQ_LEN
                 ) -> dict:
    """Reference and executor phases; raises on any failed check."""
    dev = jax.devices()[0]
    job = ManagedJob(id="olmo", tier="basic", arch=olmo.name, world_size=2,
                     total_steps=OLMO_STEPS)

    say(f"== reference: {olmo.name} L{olmo.num_layers} d_model {olmo.d_model}"
        f", {global_batch} x {seq_len} tokens, splice 1, {OLMO_STEPS} steps")
    t0 = time.perf_counter()
    ref = ElasticRuntime(olmo, job.train_config(), job.world_size,
                         job.world_size, global_batch, seq_len)
    ref_losses = [r["loss"] for r in ref.run_steps(OLMO_STEPS)]
    del ref
    say(f"  losses {ref_losses}")
    say(f"  host wall {time.perf_counter() - t0:.3f} s (compile included); "
        f"peak_bytes_in_use so far {peak_gib(dev)}")

    say(f"== executor: 2 slots, {olmo.name} (basic, world 2) + "
        f"{premium.name} premium arrivals")
    t0 = time.perf_counter()
    ex = FleetExecutor(total_slots=2)
    ex.submit(job, global_batch, seq_len, cfg=olmo)
    ex.tick()
    ex.tick()
    assert job.allocated == 2 and job.runtime.splice == 1, ex.log

    live = job.runtime  # the state the barrier quiesces is the checkpoint
    p1 = ManagedJob(id="premium-1", tier="premium", arch=premium.name,
                    world_size=2, total_steps=PREMIUM_STEPS)
    ex.submit(p1, global_batch, seq_len, cfg=premium)
    ex.tick()
    assert job.preemptions == 1 and job.runtime is None, ex.log
    ckpt_step = job.steps_done
    checkpointed = host_tree(live.state)
    del live
    assert int(checkpointed["step"]) == ckpt_step

    for _ in range(4 * PREMIUM_STEPS):
        if p1.done:
            break
        ex.tick()
    assert p1.done, ex.log

    p2 = ManagedJob(id="premium-2", tier="premium", arch=premium.name,
                    world_size=1, total_steps=PREMIUM_STEPS)
    ex.submit(p2, global_batch, seq_len, cfg=premium)
    ex.tick(steps=0)  # the restore tick, before the job steps again
    restore = ex.log[-1]
    assert restore == {"event": "restore", "job": "olmo",
                       "at_step": ckpt_step}, ex.log
    assert job.runtime.splice == 2, job.runtime.splice
    assert_bit_equal(job.runtime.state, checkpointed)
    del checkpointed
    say(f"  restore at step {ckpt_step}: state equals the checkpoint bit "
        "for bit")

    ex.run(max_ticks=8 * OLMO_STEPS)
    assert all(j.done for j in ex.jobs.values()), ex.log
    events = [e["event"] for e in ex.log]
    for needed in ("preempt", "restore", "resize"):
        assert needed in events, (needed, ex.log)
    for e in ex.log:
        say(f"  log {e}")

    steps = [r["step"] for r in job.history]
    assert steps == list(range(1, OLMO_STEPS + 1)), steps
    splices = sorted({r["splice"] for r in job.history})
    assert splices == [1, 2], splices
    losses = [r["loss"] for r in job.history]
    say(f"  {olmo.name} losses {losses}")
    say(f"  {olmo.name} splice per step {[r['splice'] for r in job.history]}")
    worst = check_losses("executor vs reference", losses, ref_losses)
    for p in (p1, p2):
        pl = [r["loss"] for r in p.history]
        assert len(pl) == PREMIUM_STEPS and all(map(math.isfinite, pl)), pl
        say(f"  {p.id} losses {pl}")
    say(f"  worst relative loss difference vs reference {worst:.3e} "
        f"(tolerance {LOSS_RTOL})")
    say(f"  host wall {time.perf_counter() - t0:.3f} s (compile included); "
        f"peak_bytes_in_use so far {peak_gib(dev)}")
    return {"events": events, "worst_rel_loss_diff": worst}


# ----------------------------------------------------------- four chips
def run_four_chips(cfg: ModelConfig, global_batch: int = GLOBAL_BATCH,
                   seq_len: int = SEQ_LEN) -> dict:
    """The sharded barrier-carrying step on a (2, 2) mesh against a
    one-chip reference on device 0; raises on any failed check."""
    devs = jax.devices()
    assert len(devs) == 4, f"needs 4 devices, found {len(devs)}"
    job = ManagedJob(id="olmo", tier="basic", arch=cfg.name, world_size=2,
                     total_steps=OLMO_STEPS)
    tcfg = job.train_config()
    pipe = DataPipeline(cfg.vocab_size, seq_len, global_batch,
                        job.world_size, seed=tcfg.seed)
    batches = []
    for _ in range(SHARDED_STEPS):
        tokens, labels = pipe.next_batch()
        batches.append({"tokens": tokens, "labels": labels})
    # kept on the host: each run places its own copy, so device 0 never
    # holds a spare state beside the reference step
    state0 = host_tree(init_train_state(cfg, tcfg,
                                        jax.random.PRNGKey(tcfg.seed)))

    def drive(step, place_state, place_batch, place_flags):
        """SHARDED_STEPS steps; a preemption is requested before step 2.
        Returns (losses, summed barrier payload per step, acquired)."""
        state = place_state(state0)
        bar = BarrierDriver(n_shards=2)
        losses, payloads = [], []
        for i, batch in enumerate(batches):
            if i == 1:
                bar.request()
            state, m = step(state, place_batch(batch), place_flags(bar.flags()))
            losses.append(float(m["loss"]))
            payloads.append([int(x) for x in np.asarray(m["barrier"])])
            bar.observe(m["barrier"])
        return losses, payloads, bar.acquired

    say(f"== sharded: {cfg.name} L{cfg.num_layers} on a (2, 2) data x model "
        f"mesh, {global_batch} x {seq_len} tokens, {SHARDED_STEPS} steps")
    t0 = time.perf_counter()
    mesh = make_mesh((2, 2), ("data", "model"))
    st_sh = to_shardings(param_specs(state0, mesh), mesh)
    b_sh = to_shardings(batch_specs(batches[0], mesh), mesh)
    f_sh = NamedSharding(mesh, P("data"))
    with mesh:
        step = jax.jit(build_train_step(cfg, tcfg, with_barrier=True,
                                        mesh=mesh),
                       in_shardings=(st_sh, b_sh, f_sh),
                       out_shardings=(st_sh, None))
        sh_losses, sh_payloads, sh_acquired = drive(
            step, lambda s: jax.device_put(s, st_sh),
            lambda b: jax.device_put(b, b_sh),
            lambda f: jax.device_put(f, f_sh))
    say(f"  losses {sh_losses}")
    say(f"  barrier payload (need, ack) summed over data shards per step "
        f"{sh_payloads}")
    say(f"  host wall {time.perf_counter() - t0:.3f} s (compile included); "
        f"peak_bytes_in_use per device so far "
        f"{[peak_gib(d) for d in devs]}")

    say(f"== one-chip reference on {devs[0]}")
    t0 = time.perf_counter()
    ref_step = jax.jit(build_train_step(cfg, tcfg, with_barrier=True))

    def on_dev0(x):
        return jax.device_put(x, devs[0])

    ref_losses, ref_payloads, ref_acquired = drive(
        ref_step, on_dev0, on_dev0, on_dev0)
    say(f"  losses {ref_losses}")
    say(f"  host wall {time.perf_counter() - t0:.3f} s (compile included); "
        f"peak_bytes_in_use on device 0 so far {peak_gib(devs[0])}")

    # step 2 carries need from both data shards, step 3 both acks
    assert sh_payloads == ref_payloads == [[0, 0], [2, 0], [2, 2]], \
        (sh_payloads, ref_payloads)
    assert sh_acquired and ref_acquired
    worst = check_losses("sharded vs one chip", sh_losses, ref_losses)
    say(f"  barrier acquired on both; worst relative loss difference "
        f"{worst:.3e} (tolerance {LOSS_RTOL})")
    return {"payloads": sh_payloads, "worst_rel_loss_diff": worst}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded (2, 2) path")
    args = ap.parse_args()
    cache = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    say(f"device {dev.device_kind} x {len(devs)}, jax {jax.__version__}, "
        f"compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(olmo_config())
    else:
        run_one_chip(olmo_config(), get_config("mamba2-130m"))
    say(f"total host wall {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
