"""Readings that set a cell's correctness limits, on the chip.

    python bench/control.py --workload <cell> --seeds 1,2,... [--control-seeds 1,2,3]

For every seed of ``--seeds`` the program runs the cell's set-up (the
followed steps; no window is needed) and is compared with the float32
reference: the lower readings.  For each of ``--control-seeds`` two more
trajectories are compared with that reference:

- ``control``: the reference itself with every matmul operand rounded to
  float8_e4m3fn, one precision below the configuration's bfloat16;
- ``half_batch``: the reference given the first half of each batch's rows
  only, the mean taken over those (a planted fault);
- ``splice_slice``, in a cell whose followed steps run at splice 2: the
  reference given the first half of the rows at those steps only, as a
  splice-2 step that runs one of its two slices would (a planted fault).

A state left unchanged reads 1 on ``change_gap`` and needs no run.  Every
reading is one JSON line on standard output.  Not part of a benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)

    import jax
    from bench import harness, spec
    from bench.references.common import Matmul
    from bench.traffic import job_seed
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    tr = cell.traffic
    follow = next(j for j in tr["jobs"] if j["id"] == tr["follow"]["job"])

    def emit(kind, seed, values, got, ref, **extra):
        """One reading, with each leaf's gaps beside the worst ones."""
        detail = {
            "grad_leaves": harness.leaf_gaps(got["grad1"], ref["grad1"]),
            "change_leaves": harness.leaf_gaps(
                got["change"], ref["change"],
                harness.moved_leaves(ref["grad1"])),
            "loss_steps": [abs(a - b) / abs(b) for a, b in
                           zip(got["losses"], ref["losses"])],
        }
        print(json.dumps({"kind": kind, "seed": seed, **values, **extra,
                          **detail}), flush=True)

    refs = {}
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = harness.Run(cell, seed)
        try:
            r.setup()
        finally:
            r.close()
        t1 = time.perf_counter()
        ref = harness.reference_readings(cell, r.follow_seed,
                                         r.follow_total_steps)
        refs[seed] = ref
        checks = harness.compare(r.readings, ref, cell.limits,
                                 r.restore_checks)
        emit("program", seed, {**harness.gaps(r.readings, ref),
                                **{c["name"]: c["value"] for c in checks}},
             r.readings, ref, setup_s=t1 - t0,
             reference_s=time.perf_counter() - t1,
             memory_peak_bytes=harness.memory_peak_bytes())
    for seed in args.control_seeds:
        fs = job_seed(seed, follow["id"])
        total = int(follow["total_steps"])
        ref = refs.get(seed) or harness.reference_readings(cell, fs, total)
        half = cell.config["train"]["global_batch"] // 2
        kinds = [("control", {"mm": Matmul("float8_e4m3fn")}),
                 ("half_batch", {"rows": half})]
        if 2 in tr["follow"]["splices"]:
            kinds.append(("splice_slice", {"rows": half, "at_splice": 2}))
        for kind, kw in kinds:
            t0 = time.perf_counter()
            got = harness.reference_readings(cell, fs, total, **kw)
            emit(kind, seed, harness.gaps(got, ref), got, ref,
                 seconds=time.perf_counter() - t0)
    print(f"control: total {time.perf_counter() - T_START:.1f} s",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
