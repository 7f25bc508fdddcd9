#!/usr/bin/env python3
"""The control of each cell's ``correct``: the plain reference computed
in the nearest lower precision (the client block's data and model rows
in bfloat16, float32 accumulation), put in the program's place and
judged by the same check as a run (``yardstick/measure.py`` ``check``),
at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \\
        [--size clients=64 --size n_examples=512 ...]

For each seed: the inputs from the seed; as many jobs as the check
compares (the mix's ``check_jobs``: jobs 0, 1, ...), each computed by
the control; then the check.  Prints one JSON line per seed with
``correct``, which the control must make false, and the numbers
compared, each beside its limit.  The benchmark's own runs never run
it.  Without a TPU it exits non-zero unless ``--cpu`` is given.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _value(text: str):
    try:
        return json.loads(text)
    except ValueError:
        return text


def control_jobs(config, traffic, inputs, seed):
    """The jobs a run's check would compare, made by the control in the
    program's place."""
    import jax.numpy as jnp

    from yardstick.measure import reference_job
    from yardstick.spec import model_of
    from yardstick.traffic import job_plan

    v0 = jnp.asarray(model_of(config).initial_vector(inputs))
    jobs = []
    for j in range(int(traffic["check_jobs"])):
        job = {"job": j, "plan": job_plan(traffic, j,
                                          int(config["clients"]))}
        low = reference_job(config, traffic, job, inputs, v0, seed,
                            low=True)
        job.update(v=low["v"], census=low["census"])
        jobs.append(job)
    return jobs


def readings(name: str, seeds, size=None, require_tpu: bool = True):
    import contextlib

    import jax

    from yardstick.measure import check, devices_for, sized
    from yardstick.spec import Cell, model_of

    cell = Cell(name)
    config, traffic = sized(cell, size)
    devs = devices_for(cell.chips, require_tpu)
    place = (jax.default_device(devs[0]) if cell.chips == 1
             else contextlib.nullcontext())
    out = []
    with place:
        for seed in seeds:
            inputs = model_of(config).inputs(seed, config)
            jobs = control_jobs(config, traffic, inputs, seed)
            checks, failed = check(cell, jobs, inputs, seed, config,
                                   traffic)
            row = {"seed": seed, "correct": all(
                c["value"] <= c["limit"] for c in checks.values()),
                "failed": failed, "attempted": len(jobs),
                "checks": checks}
            print(json.dumps(row), flush=True)
            out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--size", action="append", default=[],
                    help="key=value overriding a configuration or "
                         "traffic key")
    ap.add_argument("--cpu", action="store_true",
                    help="run without a TPU (a check of the script)")
    args = ap.parse_args(argv)
    size = {k: _value(v) for k, v in (s.split("=", 1) for s in args.size)}
    from yardstick.measure import NoChip
    try:
        readings(args.workload, [int(s) for s in args.seeds.split(",")],
                 size, require_tpu=not args.cpu)
    except NoChip as e:
        print(f"control: {e}; nothing was run", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
