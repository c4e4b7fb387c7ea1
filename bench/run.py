"""Run one benchmark cell on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no child: a chip belongs to one process.  Exits non-zero and
prints no result line when JAX finds no TPU, or fewer chips than the cell
asks for, or when the program (``src/``) is not beside the benchmark.
The last line of standard output is the result's JSON object; the compared
numbers, each beside its limit, are the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here instead of a "
                         "temporary directory")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench: the program is missing ({SRC}/repro)", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, SRC]
    from bench import harness, spec

    try:
        cell = spec.cell(args.workload)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, trace_dir=args.trace_dir)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
