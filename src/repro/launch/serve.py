"""Serving driver: replica-group planning plus a CPU-runnable smoke decode.

Two stages, matching how the scheduler treats a latency-SLO service
(docs/serving.md):

1. **Plan** — derive the replica operating point for the *full* model
   config analytically (``ReplicaProfile.from_config``: memory-fit TP
   degree, decode-roofline batch search against the p99 SLO) and print
   the qps -> replicas curve the scheduler's autoscaler walks.  Pure
   numpy; runs anywhere.
2. **Smoke** — unless ``--plan-only``, generate through the real
   ``ServingEngine`` (prefill + KV/SSM-cache decode) on the reduced smoke
   config so the decode path itself is exercised on CPU.  ``--full`` runs
   the engine on the full config instead (accelerator-sized).

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b \\
        --slo-ms 30 --qps 500
    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m \\
        --batch 4 --prompt-len 32 --decode-tokens 16
"""

from __future__ import annotations

import argparse
import time

from repro.configs import get_config, get_smoke_config
from repro.serving.engine import ReplicaProfile
from repro.utils.compile_cache import enable_compile_cache


def plan(args) -> None:
    cfg = get_config(args.arch)
    try:
        prof = ReplicaProfile.from_config(
            cfg,
            slo_ms=args.slo_ms,
            tokens_per_request=args.tokens_per_request,
        )
    except ValueError as e:
        print(f"plan: {args.arch} cannot meet p99 <= {args.slo_ms}ms: {e}")
        return
    print(
        f"plan[{cfg.name}]: slo={args.slo_ms}ms -> "
        f"{prof.gpus_per_replica} GPU(s)/replica, batch={prof.batch}, "
        f"p99 decode={prof.p99_decode_seconds * 1e3:.1f}ms, "
        f"{prof.tokens_per_second:.0f} tok/s, "
        f"{prof.qps_per_replica:.1f} qps/replica "
        f"({prof.weight_bytes / 2**30:.1f} GiB weights)"
    )
    for qps in (args.qps * f for f in (0.25, 0.5, 1.0, 1.5, 2.0)):
        n = prof.replicas_for(qps, utilization=args.target_utilization)
        print(
            f"  {qps:10.1f} qps -> {n:4d} replicas "
            f"({n * prof.gpus_per_replica} GPUs at "
            f"rho={args.target_utilization})"
        )


def smoke(args) -> None:
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import ServingEngine

    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    engine = ServingEngine(cfg, seed=0)
    key = jax.random.PRNGKey(1)
    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size, jnp.int32
    )
    t0 = time.time()
    out = engine.generate(
        prompts,
        max_new_tokens=args.decode_tokens,
        temperature=args.temperature,
    )
    wall = time.time() - t0
    print(
        f"smoke[{cfg.name}]: batch={args.batch} prompt={args.prompt_len} "
        f"decode={args.decode_tokens}"
    )
    print("generated token ids (first row):", out[0].tolist())
    print(
        f"wall {wall:.2f}s  prefill+decode compiled and ran on "
        f"{jax.device_count()} device(s)"
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--slo-ms", type=float, default=50.0)
    ap.add_argument("--tokens-per-request", type=int, default=128)
    ap.add_argument("--qps", type=float, default=1000.0)
    ap.add_argument("--target-utilization", type=float, default=0.75)
    ap.add_argument(
        "--plan-only",
        action="store_true",
        help="print the replica plan and skip the engine smoke decode",
    )
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    plan(args)
    if not args.plan_only:
        enable_compile_cache()
        smoke(args)


if __name__ == "__main__":
    main()
