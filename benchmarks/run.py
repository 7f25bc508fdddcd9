"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.
    PYTHONPATH=src python -m benchmarks.run [--only fig1a,comm,...]
"""
from __future__ import annotations

import argparse
import sys
import traceback

BENCHES = ["comm", "noise", "table3", "fig1a", "fig1b", "biased",
           "delay", "step_time", "roofline", "cohort_scale"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(BENCHES))
    args = ap.parse_args()
    selected = args.only.split(",") if args.only else BENCHES
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for name in selected:
        try:
            mod = __import__(f"benchmarks.bench_{name}",
                             fromlist=["run"])
            for row_name, us, derived in mod.run():
                print(f"{row_name},{us:.1f},\"{derived}\"", flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"bench_{name},0,\"FAILED\"", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
