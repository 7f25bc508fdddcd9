#!/usr/bin/env python3
"""Profile one cell's jobs by program span and device scope.

    python3 bench/scope_profile.py --workload <cell> --seed <n> \\
        [--keep DIR] [--out FILE]

Set-up as ``bench/run.py`` does it (the inputs from the seed, the
program, a warm-up job that compiles the segment).  Then one job runs
plain, the cell's ``trace_jobs`` jobs run under the profiler, and the
first job runs plain again, so the capture's cost reads as the traced
job's ``updates_per_s`` beside the plain ones.  The trace is reduced as
a ``--trace 1`` run reduces it, and besides by the program's own names
(``yardstick/scopes.py``): device time per scope of the compiled
segment, the share of the segment's time under no scope, and each idle
gap under the innermost harness or program span.  ``--keep`` keeps the
trace and the segment's scope table.  The last line of standard output
is the result, as JSON.  Needs the cell's chips.

A ``--trace 1`` run of ``bench/run.py`` does not read these yet; once it
does, this file goes (``PERF.md`` §7).
"""
import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def profile(name, seed, *, keep=None):
    import jax
    from yardstick import measure, scopes, trace
    from yardstick.data import seed32
    from yardstick.program import Program
    from yardstick.spec import Cell, model_of

    t_start = time.perf_counter()
    cell = Cell(name)
    chips = cell.chips
    config, traffic = cell.config, cell.traffic
    devs = measure.devices_for(chips, require_tpu=True)
    measure.enable_compile_cache()
    place = (jax.default_device(devs[0]) if chips == 1
             else contextlib.nullcontext())
    with place:
        inputs = model_of(config).inputs(seed, config)
        prog = Program(config, traffic, inputs, seed32(seed))
        # the warm-up job, as run_job runs it, keeping its wall spans
        sim, plan = prog.job(0, int(traffic["warmup_rounds"]))
        warm = sim.run(max_rounds=plan["rounds"],
                       eval_every=int(traffic["eval_every"]),
                       eval_fn=prog.task.metrics)["telemetry"].wall
        jax.block_until_ready(sim.engine.state.v)
        del sim
        setup_s = time.perf_counter() - t_start

        def rate(job):
            return job["census"]["messages"] / (job["setup_s"]
                                                + job["run_s"])

        plain = [measure.run_job(prog, 0)]
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        tdir = tempfile.mkdtemp()
        try:
            jax.profiler.start_trace(tdir, profiler_options=opts)
            ann = jax.profiler.TraceAnnotation
            jobs = [measure.run_job(prog, j, annotate=ann)
                    for j in range(int(traffic["trace_jobs"]))]
            jax.profiler.stop_trace()
            plain.append(measure.run_job(prog, 0))
            path = trace.find_xplane(tdir)
            devices, programs, spans = trace.read(path, chips)
            spans = spans + scopes.program_spans(path)
            if keep:
                os.makedirs(keep, exist_ok=True)
                shutil.copy(path, os.path.join(keep, os.path.basename(path)))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        sim, _ = prog.job(0)
        table = sim.engine.segment_scopes()
        del sim
        prog.release()

    window = trace.window_of(spans, "bench.job_setup", "bench.job_run")
    red = trace.reduce(devices, spans, window, programs)
    sc = scopes.scope_times(devices, window, table, programs)
    rounds = sum(j["rounds"] for j in jobs)
    seg = sc["segment_s"]
    metrics = program_metrics(spans, window, warm)
    if rounds and seg > 0:
        metrics.update(
            client_block_ms_per_round=1e3 * sc["client_block_s"] / rounds,
            kernel_layout_ms_per_round=1e3 * sc["kernel_layout_s"] / rounds)
    in_window = {}
    for n, s, e in spans:
        if n.startswith(scopes.PROGRAM_PREFIX) and s >= window[0] \
                and e <= window[1]:
            in_window[n] = in_window.get(n, 0.0) + (e - s) * 1e-9
    busy = red["busy_s"]
    if keep:
        seen = {scopes.instruction(n) for ops in devices.values()
                for n, _, _ in ops}
        with open(os.path.join(keep, "scopes.json"), "w") as f:
            json.dump({k: v for k, v in sorted(table.items()) if k in seen},
                      f, indent=0)
    return {
        "cell": name, "chips": chips, "device_kind": devs[0].device_kind,
        "setup_s": setup_s, "rounds": rounds, "metrics": metrics,
        "capture": {"plain_updates_per_s": [rate(j) for j in plain],
                    "traced_updates_per_s": [rate(j) for j in jobs]},
        "window_s": red["window_s"], "busy_s": busy,
        "per_class_s": red["per_class_s"],
        "device_ops": scopes.top(red["per_op_s"]),
        "segment_s": seg, "other_programs_s": sc["other_programs_s"],
        "unscoped_share": sc["unscoped_s"] / seg if seg else None,
        "unscoped_ops": [[k, v] for k, v in
                         scopes.top(sc["unscoped_ops_s"], 50)
                         if v > 0.01 * busy],
        "device_scopes": scopes.top(sc["per_scope_s"]),
        "per_chip_scopes": {str(i): scopes.top(d, 6)
                            for i, d in sc["per_chip_s"].items()},
        "per_chip_busy_s": {
            str(i): 1e-9 * sum(e - s for s, e in trace.union(
                [(s, e) for _, s, e in trace.clip(
                    programs.get(i) or ops, *window)]))
            for i, ops in devices.items()},
        "idle_gaps": red["idle_gaps"][:10],
        "program_spans_s": dict(sorted(in_window.items(),
                                       key=lambda x: -x[1])),
    }


def program_metrics(spans, window, warmup_wall):
    """The per-layer metrics the program's host spans give: the mean
    ``cohort.engine_init`` and ``cohort.report`` span inside the window
    (one of each per job), in ms, and the segment's compile in s, the
    warm-up's (its ``MetricsReport.wall``) and any inside the window."""
    w0, w1 = window

    def inside(name):
        return [(e - s) * 1e-9 for n, s, e in spans
                if n == name and s >= w0 and e <= w1]

    out = {}
    for metric, name in (("engine_init_ms", "cohort.engine_init"),
                         ("report_ms_per_job", "cohort.report")):
        secs = inside(name)
        if secs:
            out[metric] = 1e3 * sum(secs) / len(secs)
    if "compile_s" in warmup_wall or inside("cohort.compile"):
        out["segment_compile_s"] = (warmup_wall.get("compile_s", 0.0)
                                    + sum(inside("cohort.compile")))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--keep", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from yardstick.measure import NoChip
    try:
        res = profile(args.workload, args.seed, keep=args.keep)
    except NoChip as e:
        print(f"scope_profile: {e}; nothing was run", file=sys.stderr)
        return 1
    line = json.dumps(res)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
