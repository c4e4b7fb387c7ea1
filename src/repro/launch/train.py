"""End-to-end elastic training driver (CPU-runnable).

Trains a reduced-config model for N steps through the FULL Singularity
stack: elastic runtime (logical world size, splice factor), in-graph
barrier, periodic transparent checkpoints, and optional mid-run resizes —
the paper's §2 lifecycle as one command.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b \
        --steps 60 --world 4 --physical 4 --resize 20:2 --resize 40:4
"""
from __future__ import annotations

import argparse
import json
import time

from repro.configs import get_config, get_smoke_config
from repro.configs.base import TrainConfig
from repro.core.checkpoint import CheckpointStore
from repro.core.elastic import ElasticRuntime
from repro.core.migration import checkpoint_job
from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--full", action="store_true",
                    help="use the full config (default: reduced smoke)")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--world", type=int, default=4,
                    help="logical world size (constant for the job)")
    ap.add_argument("--physical", type=int, default=4)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resize", action="append", default=[],
                    help="step:new_physical (repeatable)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    tcfg = TrainConfig(total_steps=args.steps, warmup_steps=2,
                       learning_rate=args.lr)
    resizes = {}
    for r in args.resize:
        step, phys = r.split(":")
        resizes[int(step)] = int(phys)

    rt = ElasticRuntime(cfg, tcfg, args.world, args.physical,
                        args.global_batch, args.seq_len)
    store = CheckpointStore()
    t0 = time.time()
    events = []
    while int(rt.state["step"]) < args.steps:
        step = int(rt.state["step"])
        if step in resizes:
            ev = rt.resize(resizes[step])
            print(f"[resize] {ev}")
            events.append({"resize": ev})
        if args.ckpt_every and step and step % args.ckpt_every == 0:
            stats = checkpoint_job(rt, store, f"train-{args.arch}")
            print(f"[ckpt] step={step} stored={stats.device_stored_bytes/1e6:.1f}MB "
                  f"(logical {stats.device_logical_bytes/1e6:.1f}MB, "
                  f"{stats.n_workers} workers)")
        rec = rt.run_steps(1)[0]
        print(f"step {rec['step']:4d} loss={rec['loss']:.4f} "
              f"splice={rec['splice']} physical={rec['physical']}")
    wall = time.time() - t0
    print(f"done: {args.steps} steps in {wall:.1f}s "
          f"(compile {rt.compile_seconds:.1f}s)")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"history": rt.history, "events": events,
                       "wall_seconds": wall}, f, indent=2)


if __name__ == "__main__":
    main()
