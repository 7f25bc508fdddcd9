"""One run of one cell: set-up, warm-up, the measured window, the check.

    set-up    JAX and the chip, the inputs from the seed, the program's
              task, and a warm-up job with the shapes of every job
              (compiles the segment and the eval's ops)
    window    a closed loop of jobs for ``seconds`` (``trace=False``),
              or ``trace_jobs`` jobs under the profiler (``trace=True``)
    check     once the window has closed and the program's state is
              freed: a sample of the window's jobs, drawn from the seed,
              recomputed by the plain reference of the mix's protocol
              (``bench/references/<model>_<reference>.py``)

The last lines of standard error are the numbers compared, each beside
its limit; the last line of standard output is the result, as JSON.
"""
from __future__ import annotations

import contextlib
import gc
import json
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import counts, reference
from . import trace as trace_mod
from .data import seed32
from .peaks import PEAKS, peaks
from .program import Program, import_program
from .spec import Cell, metric_reader, model_of, reference_of


class NoChip(RuntimeError):
    pass


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devs[0].platform!r})")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``repro.compile_cache``:
    the directory ``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache``
    at the root of the checkout), keeping every program it compiles."""
    import jax
    import_program()
    from repro.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _annotate(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def run_job(prog: Program, j: int, *, rounds: Optional[int] = None,
            annotate=None) -> Dict[str, Any]:
    """One job, timed on the host clock; keeps its census and model."""
    import jax
    annotate = annotate or _annotate(False)
    t0 = time.perf_counter()
    with annotate("bench.job_setup"):
        sim, plan = prog.job(j, rounds)
    t1 = time.perf_counter()
    stamps: List[float] = []

    def eval_fn(model):
        with annotate("bench.eval"):
            m = prog.task.metrics(model)
        stamps.append(time.perf_counter())
        return m

    with annotate("bench.job_run"):
        res = sim.run(max_rounds=plan["rounds"],
                      eval_every=int(prog.traffic["eval_every"]),
                      eval_fn=eval_fn)
        jax.block_until_ready(res["model"])
    t2 = time.perf_counter()
    tel = res["telemetry"]
    hist = [int(h["round"]) for h in res["history"]]
    segs = np.diff([t1] + stamps[:len(hist)])
    steps = np.diff([0] + hist)
    census = {"messages": int(tel.messages),
              "broadcasts": int(tel.broadcasts), "ticks": int(tel.ticks),
              "ops": {k: int(v) for k, v in tel.ops.items()},
              "client_steps": counts.client_steps(tel.participation,
                                                  plan["sizes"])}
    out = {"job": j, "plan": plan, "setup_s": t1 - t0, "run_s": t2 - t1,
           "segments_s": [float(x) for x in segs],
           "multi_round_segments": int(np.sum(
               steps > int(prog.traffic["eval_every"]))),
           "rounds": int(res["final"]["round"]), "census": census,
           "eval_s": float(tel.wall.get("eval_s", 0.0)),
           "v": np.asarray(sim.engine.state.v)}
    del sim, res
    return out


def check(cell: Cell, jobs: List[Dict], inputs, seed: int, config,
          traffic) -> Dict[str, Dict[str, float]]:
    """Recompute a sample of the jobs with the reference; -> (the numbers
    compared, each with its limit; how many sampled jobs failed)."""
    import jax.numpy as jnp
    v0 = model_of(config).initial_vector(inputs)
    rng = np.random.default_rng(seed32(seed))
    n = min(int(traffic["check_jobs"]), len(jobs))
    picked = sorted(rng.choice(len(jobs), size=n, replace=False))
    lim = cell.limits
    gaps, mismatch, failed = [], 0, 0
    for p in picked:
        job = jobs[p]
        ref = reference_job(config, traffic, job, inputs,
                            jnp.asarray(v0), seed)
        g = reference.gap(job["v"], ref["v"], v0)
        m = sum(int(job["census"][k] != ref["census"][k])
                for k in ref["census"])
        gaps.append(g)
        mismatch += m
        failed += int(g > lim["model_gap"]["limit"]
                      or m > lim["census_mismatch"]["limit"])
    return {"model_gap": {"value": max(gaps),
                          "limit": lim["model_gap"]["limit"]},
            "census_mismatch": {"value": mismatch,
                                "limit": lim["census_mismatch"]["limit"]}
            }, failed


def reference_job(config, traffic, job, inputs, v0, seed: int,
                  low: bool = False) -> Dict:
    """The job as the plain reference of the mix's protocol computes it
    (``bench/references/<model>_<reference>.py``): its final server
    model ``v`` and its census.  ``low``: the control."""
    return reference_of(config, traffic).run(config, traffic, job, inputs,
                                             v0, seed, low=low)


def sized(cell: Cell, size: Optional[Dict[str, Any]]):
    """The cell's configuration and traffic, with ``size`` overriding
    keys of either (tests run the same code at a small size)."""
    config, traffic = dict(cell.config), dict(cell.traffic)
    for k, v in (size or {}).items():
        (config if k in config else traffic)[k] = v
    return config, traffic


def layer_context(config, traffic, jobs, reduced, kind, chips):
    """What the per-layer readers (``bench/metrics/<name>.py``) read."""
    census = {"messages": 0, "broadcasts": 0, "client_steps": 0,
              "ops": {}}
    for job in jobs:
        for k in ("messages", "broadcasts", "client_steps"):
            census[k] += job["census"][k]
        for k, v in job["census"]["ops"].items():
            census["ops"][k] = census["ops"].get(k, 0) + v
    return {"jobs": jobs, "rounds": sum(j["rounds"] for j in jobs),
            "evals": sum(len(j["segments_s"]) + 1 for j in jobs),
            "census": census, "trace": reduced, "config": config,
            "traffic": traffic, "peaks": PEAKS.get(kind), "chips": chips,
            "D": model_of(config).width(config),
            "work": model_of(config).work(config),
            "dp": bool(config.get("dp"))}


def run(name: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, size: Optional[Dict[str, Any]] = None,
        t_start: Optional[float] = None, out=None,
        compile_cache: bool = True) -> Dict[str, Any]:
    """Run one cell; print and return the result line.  Raises NoChip
    (before any result) when the chip the cell needs is not there."""
    t_start = time.perf_counter() if t_start is None else t_start
    out = out or sys.stdout
    cell = Cell(name)
    config, traffic = sized(cell, size)
    import jax
    devs = devices_for(cell.chips, require_tpu)
    kind = devs[0].device_kind
    if require_tpu:
        peaks(kind)                         # an unknown chip is an error
    cache = enable_compile_cache() if compile_cache else "off"
    _log(f"device: {devs[0].platform} {kind} x{len(devs)}; compile cache "
         f"{cache}")
    place = (jax.default_device(devs[0]) if cell.chips == 1
             else contextlib.nullcontext())
    with place:
        inputs = model_of(config).inputs(seed, config)
        jax.block_until_ready(inputs)
        prog = Program(config, traffic, inputs, seed32(seed))
        run_job(prog, 0, rounds=int(traffic["warmup_rounds"]))
        setup_s = time.perf_counter() - t_start
        _log(f"setup {setup_s:.3f} s")

        jobs: List[Dict] = []
        reduced = None
        if trace:
            # device ops and the harness's spans; no Python tracer,
            # whose per-call events would slow the host it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            with tempfile.TemporaryDirectory() as tdir:
                jax.profiler.start_trace(tdir, profiler_options=opts)
                ann = _annotate(True)
                for j in range(int(traffic["trace_jobs"])):
                    jobs.append(run_job(prog, j, annotate=ann))
                jax.profiler.stop_trace()
                dev_ops, programs, spans = trace_mod.read(
                    trace_mod.find_xplane(tdir), cell.chips)
            window = trace_mod.window_of(spans, "bench.job_setup",
                                         "bench.job_run")
            reduced = trace_mod.reduce(dev_ops, spans, window, programs)
            window_s = reduced["window_s"]
        else:
            t0 = time.perf_counter()
            j = 0
            while True:
                jobs.append(run_job(prog, j))
                j += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            window_s = time.perf_counter() - t0
        mem = [d.memory_stats() or {} for d in devs]
        peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
        prog.release()
        del prog
        gc.collect()
        jax.clear_caches()
        checks, failed = check(cell, jobs, inputs, seed, config, traffic)

    correct = all(c["value"] <= c["limit"] for c in checks.values())
    segs = np.concatenate([j["segments_s"] for j in jobs])
    multi = sum(j["multi_round_segments"] for j in jobs)
    _log(f"window {window_s:.3f} s: {len(jobs)} jobs, {len(segs)} "
         f"segments ({multi} advanced more than one eval interval)")
    metrics: Dict[str, Dict[str, Any]] = {}
    if not trace:
        values = {
            "updates_per_s": sum(j["census"]["messages"]
                                 for j in jobs) / window_s,
            "round_ms_p95": float(np.percentile(segs, 95)) * 1e3,
            "setup_s": setup_s}
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = layer_context(config, traffic, jobs, reduced, kind,
                            cell.chips)
        for m in cell.per_layer():
            val = metric_reader(m["name"])(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": len(jobs),
        "failed": failed, "metrics": metrics,
        "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        top = sorted(reduced["per_op_s"].items(), key=lambda x: -x[1])
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in top[:10]],
            "idle_gaps": [[k, v] for k, v in reduced["idle_gaps"][:10]]}
    result["checks"] = checks
    for k, c in checks.items():
        _log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), file=out, flush=True)
    return result
