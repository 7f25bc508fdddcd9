#!/usr/bin/env python3
"""Run one cell of the benchmark (BENCHMARK.json) on the chips of this
machine and print its result as the last line of standard output.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

--trace 0 reports the cell's end-to-end metrics, --trace 1 its
per-layer metrics from a profiler trace of a few whole jobs.  Without
a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.  See bench/yardstick/measure.py for what a run
does.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from yardstick.measure import NoChip, run
    try:
        run(args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
