"""The one generator of simulation jobs, driven by a traffic file.

A run is a closed loop of jobs.  A job is R rounds of the protocol on a
fresh simulator over the cell's fleet, with an eval after every round.
Job j takes the j-th entry of the mix's step sweep (cycling), so every
seed gives the same set of jobs and sizes; the seed changes the data,
the initial model, the local samples and the scenario's draws.

Keys of a traffic file (``bench/traffic/<name>.json``):

    fl               fields of the program's ``FLConfig`` (engine
                     "device" is set): ``cohort_block`` (local steps a
                     client of speed 1 takes per tick), ``scenario``
                     (preset of ``repro.scenarios``), ``d`` (staleness
                     gate: a client computes round i only while
                     i < k + d, k the freshest broadcast it holds),
                     ``aggregation`` (server strategy) and any other
    simulator        further keyword arguments of ``make_simulator``
                     (``dp_rng``, ``fuse_ticks``, ...)
    rounds_per_job   R
    eval_every       rounds between evals (the segment length)
    sizes            local steps per round: {"kind": "constant", "s"}
                     or {"kind": "affine_ceil", "s0", "a"}:
                     s_i = s0 + ceil(a * i)
    step             round step sizes: {"kind": "constant"} or
                     {"kind": "inv_t", "beta"}, both with a
                     "server_step" sweep; eta_i = server_step *
                     f(t_i) / C with f = 1 or 1 / (1 + beta * t_i),
                     t_i = sum of s_j for j < i (the paper's round
                     transform of its inv_t schedule)
    warmup_rounds    rounds of the warm-up job (same shapes as a job)
    check_jobs       jobs of the window compared with the reference
    trace_jobs       jobs a --trace 1 run traces
    reference        the protocol's plain reference:
                     ``bench/references/<model>_<reference>.py``
    fleet            the fleet as the reference draws it: ``speeds`` and
                     ``availability``, each with a ``kind`` that names
                     ``bench/fleets/<speeds|availability>/<kind>.py``,
                     and ``latency_ticks``
"""
from __future__ import annotations

import math
from typing import Any, Dict, List


def sizes(traffic: Dict[str, Any], rounds: int) -> List[int]:
    spec = traffic["sizes"]
    if spec["kind"] == "constant":
        return [int(spec["s"])] * rounds
    if spec["kind"] == "affine_ceil":
        return [int(spec["s0"]) + int(math.ceil(spec["a"] * i))
                for i in range(rounds)]
    raise ValueError(f"unknown sizes kind {spec['kind']!r}")


def round_steps(traffic: Dict[str, Any], sizes_: List[int],
                server_step: float, clients: int) -> List[float]:
    spec = traffic["step"]
    out, t = [], 0
    for s in sizes_:
        if spec["kind"] == "constant":
            f = 1.0
        elif spec["kind"] == "inv_t":
            f = 1.0 / (1.0 + spec["beta"] * t)
        else:
            raise ValueError(f"unknown step kind {spec['kind']!r}")
        out.append(server_step * f / clients)
        t += s
    return out


def job_server_step(traffic: Dict[str, Any], j: int) -> float:
    sweep = traffic["step"]["server_step"]
    return float(sweep[j % len(sweep)])


def job_plan(traffic: Dict[str, Any], j: int, clients: int,
             rounds: int = None) -> Dict[str, Any]:
    """Sizes and step sizes of job j (the schedule always has the
    job's full length R, so a shorter warm-up job has the same shapes)."""
    R = int(traffic["rounds_per_job"])
    sz = sizes(traffic, R)
    step = job_server_step(traffic, j)
    return {"sizes": sz, "etas": round_steps(traffic, sz, step, clients),
            "server_step": step, "rounds": R if rounds is None else rounds}
