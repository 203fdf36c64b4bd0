"""Run one cell of the benchmark once:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which fails without a TPU (or with fewer chips than the cell
asks for) before any model work and then prints no result. The last line of
standard output is the result object.
"""
import time

_T_START = time.perf_counter()

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import harness

    try:
        harness.configure_compile_cache(ROOT)
        result = harness.run_cell(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace), t_start=_T_START
        )
    except harness.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
