"""Perf hillclimb runner: lower a pair under a named variant and diff the
roofline terms against the baseline.

    PYTHONPATH=src python -m repro.launch.hillclimb \
        --arch yi-9b --shape decode_32k --variant donate

Variants (the §Perf candidate set):
  baseline          — as the sweep
  donate            — donate the state (input/output buffer aliasing)
  splice2/4/8       — time-slice the step (activation live-set control)
  noremat           — disable activation checkpointing
  donate+spliceN    — combined
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"  # host devices only: never claim a chip

import argparse
import json

from repro.launch.dryrun import lower_pair


def run_variant(arch: str, shape: str, mesh: str, variant: str) -> dict:
    kw = dict(splice=1, remat=True, donate=False, remat_policy="full",
              shard_profile="default", moe_capacity_factor=None,
              fused_gate=False, mesh_override=None)
    for part in variant.split("+"):
        if part.startswith("splice"):
            kw["splice"] = int(part[len("splice"):])
        elif part == "noremat":
            kw["remat"] = False
        elif part == "donate":
            kw["donate"] = True
        elif part == "dotsremat":
            kw["remat_policy"] = "dots"
        elif part == "nomodeltp":
            kw["shard_profile"] = "replicate_model"
        elif part.startswith("cf"):
            kw["moe_capacity_factor"] = float(part[2:]) / 100.0
        elif part == "fusedgate":
            kw["fused_gate"] = True
        elif part.startswith("chips"):
            n = int(part[len("chips"):])
            # right-size the mesh: keep data=16 (batch sharding), shrink TP
            kw["mesh_override"] = (16, n // 16) if n >= 16 else (n, 1)
        elif part == "baseline":
            pass
        else:
            raise ValueError(part)
    return lower_pair(arch, shape, multi_pod=(mesh == "multi"),
                      extra_tags={"variant": variant}, **kw)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--variant", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    rec = run_variant(args.arch, args.shape, args.mesh, args.variant)
    out = args.out or (f"results/perf/{args.arch}.{args.shape}."
                       f"{args.mesh}.{args.variant}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rec, f, indent=2, default=str)
    rf = rec["roofline"]
    print(f"{args.arch} x {args.shape} [{args.mesh}] variant={args.variant}")
    print(f"  compute={rf['compute_s']:.4g}s memory={rf['memory_s']:.4g}s "
          f"collective={rf['collective_s']:.4g}s dominant={rf['dominant']} "
          f"useful={rf['useful_flop_ratio']:.3f}")
    if rec.get("memory"):
        print(f"  temp {rec['memory']['temp_size_in_bytes']/1e9:.2f} GB "
              f"args {rec['memory']['argument_size_in_bytes']/1e9:.2f} GB "
              f"alias {rec['memory'].get('alias_size_in_bytes',0)/1e9:.2f} GB")


if __name__ == "__main__":
    main()
