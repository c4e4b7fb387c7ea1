import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # host devices only: never claim a chip

"""Multi-pod dry-run: lower + compile every (arch x shape) on the
production meshes and extract the roofline terms.

Usage:
    python -m repro.launch.dryrun --arch yi-9b --shape train_4k \
        --mesh single --splice 1 --out results/yi-9b.train_4k.single.json

The two environment lines above MUST run before any jax import: jax locks
the platform and device count at first init.  Pinning the CPU platform
keeps this tool (and every child ``dryrun_all`` starts) off an attached
TPU, which belongs to one process at a time.  Smoke tests and benchmarks
do NOT import this module, so they see the 1 real CPU device.
"""

import argparse
import json
import time
from typing import Dict, Optional

import jax

from repro.analysis.hlo import op_histogram
from repro.analysis.hlo_cost import analyze_hlo
from repro.analysis.roofline import build_report
from repro.configs.base import TrainConfig
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.specs import (decode_specs, input_specs, plan_pair,
                                state_specs)
from repro.models import decode_step_fn, prefill_fn
from repro.parallel.sharding import (batch_specs, decode_state_specs,
                                     param_specs, to_shardings)
from repro.training.step import build_train_step


def _cost_dict(compiled) -> Dict:
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost) if cost else {}


def _memory_stats(compiled) -> Optional[Dict]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for name in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        if hasattr(ma, name):
            out[name] = int(getattr(ma, name))
    out["bytes_per_device"] = (out.get("argument_size_in_bytes", 0)
                               + out.get("output_size_in_bytes", 0)
                               + out.get("temp_size_in_bytes", 0)
                               - out.get("alias_size_in_bytes", 0))
    return out


def lower_pair(arch: str, shape_name: str, multi_pod: bool,
               splice: int = 1, remat: bool = True, donate: bool = False,
               remat_policy: str = "full", shard_profile: str = "default",
               moe_capacity_factor: Optional[float] = None,
               fused_gate: bool = False,
               mesh_override: Optional[tuple] = None,
               extra_tags: Optional[Dict] = None) -> Dict:
    """Lower + compile one pair on one mesh; returns the result record."""
    import dataclasses as _dc

    from repro.models import moe as _moe
    from repro.parallel import constraints as _constraints

    plan = plan_pair(arch, shape_name)
    mesh_name = "multi" if multi_pod else "single"
    if plan.skip_reason:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": plan.skip_reason}
    cfg, shape = plan.cfg, plan.shape
    if moe_capacity_factor is not None and cfg.moe is not None:
        cfg = _dc.replace(cfg, moe=_dc.replace(
            cfg.moe, capacity_factor=moe_capacity_factor))
    _moe.FUSED_GATE = fused_gate
    _constraints.DISABLE_MODEL_CONSTRAINTS = (shard_profile == "replicate_model")
    if mesh_override is not None:
        mesh = make_mesh(mesh_override, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    tcfg = TrainConfig(remat=remat, remat_policy=remat_policy)
    t0 = time.time()

    with mesh:
        if shape.kind == "train":
            state = state_specs(cfg, tcfg)
            batch = input_specs(cfg, shape)
            st_sh = to_shardings(param_specs(state, mesh, shard_profile), mesh)
            b_sh = to_shardings(batch_specs(batch, mesh), mesh)
            step = build_train_step(cfg, tcfg, splice=splice)
            kw = {"donate_argnums": (0,)} if donate else {}
            lowered = jax.jit(
                step, in_shardings=(st_sh, b_sh),
                out_shardings=(st_sh, None), **kw).lower(state, batch)
        elif shape.kind == "prefill":
            state = state_specs(cfg, tcfg)
            params = state["params"]
            batch = input_specs(cfg, shape)
            p_sh = to_shardings(param_specs(params, mesh, shard_profile), mesh)
            b_sh = to_shardings(batch_specs(batch, mesh), mesh)
            dstate = decode_specs(cfg, shape)
            d_sh = to_shardings(
                decode_state_specs(dstate, mesh, shape.global_batch,
                                   shard_profile), mesh)
            def fn(p, b):
                return prefill_fn(p, b, cfg, remat=remat)
            lowered = jax.jit(
                fn, in_shardings=(p_sh, b_sh),
                out_shardings=(None, d_sh)).lower(params, batch)
        else:  # decode
            state = state_specs(cfg, tcfg)
            params = state["params"]
            p_sh = to_shardings(param_specs(params, mesh, shard_profile), mesh)
            dstate = decode_specs(cfg, shape)
            d_sh = to_shardings(
                decode_state_specs(dstate, mesh, shape.global_batch,
                                   shard_profile), mesh)
            tok = input_specs(cfg, shape)["token"]
            t_sh = to_shardings(batch_specs({"t": tok}, mesh), mesh)["t"]
            def fn(p, s, t):
                return decode_step_fn(p, s, t, cfg)
            kw = {"donate_argnums": (1,)} if donate else {}
            lowered = jax.jit(
                fn, in_shardings=(p_sh, d_sh, t_sh),
                out_shardings=(None, d_sh), **kw).lower(params, dstate, tok)

        lower_s = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t1

    _moe.FUSED_GATE = False
    _constraints.DISABLE_MODEL_CONSTRAINTS = False
    cost = _cost_dict(compiled)
    mem = _memory_stats(compiled)
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    hc = analyze_hlo(hlo)
    report = build_report(arch, shape, mesh_name, chips, cost, hlo, cfg, mem,
                          hlo_cost=hc)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "chips": chips, "splice": splice,
        "swa_variant": plan.swa_variant,
        "lower_seconds": round(lower_s, 2),
        "compile_seconds": round(compile_s, 2),
        "memory": mem,
        "xla_cost_analysis": {k: float(v) for k, v in cost.items()
                              if isinstance(v, (int, float))},
        "hlo_cost": hc.as_dict(),
        "roofline": report.row(),
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "hlo_ops": {k: v for k, v in sorted(
            op_histogram(hlo).items(), key=lambda kv: -kv[1])[:25]},
    }
    if extra_tags:
        rec.update(extra_tags)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--splice", type=int, default=1)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--donate", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    rec = lower_pair(args.arch, args.shape, multi_pod=(args.mesh == "multi"),
                     splice=args.splice, remat=not args.no_remat,
                     donate=args.donate)
    text = json.dumps(rec, indent=2, default=str)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    if rec.get("status") == "ok":
        print(f"{args.arch} x {args.shape} [{args.mesh}] OK "
              f"chips={rec['chips']} "
              f"compile={rec['compile_seconds']}s "
              f"dominant={rec['roofline']['dominant']}")
        if rec.get("memory"):
            print("memory_analysis:", rec["memory"])
        print("hlo_cost:", {k: f"{v:.3e}" for k, v in
                            rec["hlo_cost"].items()
                            if isinstance(v, float)})
        print("roofline:", {k: (f"{v:.4g}" if isinstance(v, float) else v)
                            for k, v in rec["roofline"].items()
                            if k in ("compute_s", "memory_s", "collective_s",
                                     "dominant", "useful_flop_ratio")})
    else:
        print(f"{args.arch} x {args.shape} [{args.mesh}] SKIPPED: "
              f"{rec['reason']}")
    if not args.out:
        print(text)


if __name__ == "__main__":
    main()
