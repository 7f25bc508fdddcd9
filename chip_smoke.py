#!/usr/bin/env python3
"""Smoke run of the device engine on a TPU: the quickest proof that the
repo's main path compiles and runs on the chip.

    python chip_smoke.py             # one chip: phases logreg, logreg_dp, model
    python chip_smoke.py --chips 4   # phase logreg with the client axis
                                     # sharded over four chips, against
                                     # the same run on one chip

Every phase goes through ``make_simulator(FLConfig(engine="device"), ...)``
and checks what comes out.  Earlier lines report each phase; the last
line of stdout is ``{"ok": true, "device": {...}}``, printed only when
every phase passed.  The script exits non-zero without that line when
JAX finds no TPU, when the repo's ``src/`` is not beside it, or when a
phase raises or misses a check.  It never falls back to the CPU.  The
seconds it prints are smoke timings of one run, not benchmark metrics.
Everything runs in this one process, which owns the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

SEED = 0
C_LOGREG = 4096          # clients of the logreg phases
N_FEATURES = 784         # MNIST's width: D = 784 + bias = 785
N_EXAMPLES = 4096
ROUNDS = 8
SERVER_STEP = 1.0        # eta * C: the FedSGD server step per round
TICK_KERNELS = ("tick_deliver", "bucket_apply", "tick_scatter")


def log(msg: str) -> None:
    print(msg, flush=True)


def model_tolerance(v_ref, C: int) -> float:
    """Worst-case f32 error of the runs' differing sums: a sum of C terms
    may round C times (C * 2^-24 of its largest partial sum), once per
    round, scaled by the model's magnitude.  Census and schedule are
    integer and compared exactly; only these add orders differ (kernel C
    tiles and shards against XLA's reduction)."""
    import numpy as np
    return ROUNDS * C * 2.0 ** -24 * max(1.0, float(np.abs(v_ref).max()))


def census(tel) -> dict:
    return {"messages": int(tel.messages),
            "broadcasts": int(tel.broadcasts),
            "ticks": int(tel.ticks),
            "ops": {k: int(v) for k, v in tel.ops.items()},
            "staleness_hist": [int(x) for x in tel.staleness_hist],
            "participation": [int(x) for x in tel.participation],
            "bytes_up": int(sum(int(x) for x in tel.bytes_up))}


class Logreg:
    """The paper's logistic regression at D = 785 on synthetic data."""

    def __init__(self, *, dp: bool, C: int = C_LOGREG):
        from repro.configs.base import FLConfig
        from repro.configs.paper_logreg import fl_config_fig1b
        from repro.core import LogRegTask
        from repro.data import make_binary_dataset
        X, y = make_binary_dataset(N_EXAMPLES, N_FEATURES, seed=SEED)
        fig1b = fl_config_fig1b().dp          # sigma 8, clip 0.1
        dp_kw = (dict(dp_clip=fig1b.clip_norm, dp_sigma=fig1b.sigma)
                 if dp else {})
        self.task = LogRegTask(X, y, l2=1.0 / N_EXAMPLES, sample_seed=SEED,
                               **dp_kw)
        self.C = C
        self.cfg = FLConfig(engine="device", cohort_block=1,
                            scenario="mobile_diurnal" if dp else "uniform")
        self.kw = dict(n_clients=C, sizes_per_client=[1] * ROUNDS,
                       round_stepsizes=[SERVER_STEP / C] * ROUNDS,
                       d=3 if dp else 1, seed=SEED)

    def device(self, **extra):
        from repro.cohort import make_simulator
        return make_simulator(self.cfg, self.task, **self.kw, **extra)

    def host(self):
        from repro.cohort import make_simulator
        from repro.configs.base import FLConfig
        cfg = FLConfig(engine="cohort", cohort_block=self.cfg.cohort_block,
                       scenario=self.cfg.scenario)
        return make_simulator(cfg, self.task, **self.kw)


def on_chips(chips: int):
    """Context in which a device engine is built over ``chips`` chips:
    one pins JAX's default device, which ``repro.sharding.cohort_mesh``
    takes; more leaves the engine every visible chip."""
    import contextlib
    import jax
    return (jax.default_device(jax.devices()[0]) if chips == 1
            else contextlib.nullcontext())


def run_device(spec: Logreg, chips: int, **extra):
    """Build over ``chips`` chips, run ROUNDS rounds with an eval per
    round; return (sim, result)."""
    with on_chips(chips):
        sim = spec.device(**extra)
    used = sim.engine.mesh.devices.size
    if used != chips:
        raise RuntimeError(f"engine sharded over {used} chips, not {chips}")
    res = sim.run(max_rounds=ROUNDS, eval_every=1)
    return sim, res


def segment_text(sim) -> str:
    """Compiled HLO of the engine's jitted segment, lowered with the
    shardings the run used (a persistent-cache hit after the run)."""
    import jax
    import numpy as np
    eng = sim.engine
    bound = jax.device_put(np.int32(0), eng._replicated)
    return eng._segment_fn().lower(
        eng.state, eng._etas_dev, eng._sizes_dev, eng._accrual_dev,
        bound, bound).compile().as_text()


def check_engine_run(sim, res, *, want_kernels, problems):
    """Checks every device-engine logreg run must pass."""
    import numpy as np
    from repro.telemetry.costs import check_ops, pallas_kernels_in
    tel = res["telemetry"]
    li, bi = sim.engine.fused_iters
    problems += check_ops(tel.ops, messages=tel.messages,
                          broadcasts=tel.broadcasts,
                          far_messages=tel.far_messages, clients=sim.engine.C,
                          ticks=tel.ticks, loop_iters=li, block_iters=bi)
    if res["final"]["round"] != ROUNDS:
        problems.append(f"ran {res['final']['round']} of {ROUNDS} rounds")
    v = np.asarray(sim.engine.state.v)
    if not np.isfinite(v).all():
        problems.append("non-finite server model")
    found = pallas_kernels_in(segment_text(sim))
    missing = sorted(set(want_kernels) - found)
    log(f"  kernels in the compiled segment: {sorted(found)}")
    if missing:
        problems.append(f"no tpu_custom_call for {missing}: a reference "
                        "path stands in for the kernel")
    return v


def compare(name, v, v_ref, cen, cen_ref, C, problems):
    import numpy as np
    if cen != cen_ref:
        diff = sorted(k for k in cen if cen[k] != cen_ref[k])
        problems.append(f"{name}: integer census differs in {diff}")
    else:
        log(f"  {name}: integer census equal ({cen['messages']} messages, "
            f"{cen['broadcasts']} broadcasts, {cen['ticks']} ticks)")
    err = float(np.abs(v - v_ref).max())
    tol = model_tolerance(v_ref, C)
    log(f"  {name}: model max |diff| {err!r} (tolerance {tol!r})")
    if not err <= tol:
        problems.append(f"{name}: model differs by {err} > {tol}")


def phase_logreg(problems):
    import numpy as np
    spec = Logreg(dp=False)
    loss0 = spec.task.metrics(spec.task.init_model())["loss"]
    sim, res = run_device(spec, 1)
    v = check_engine_run(sim, res, want_kernels=TICK_KERNELS,
                         problems=problems)
    losses = [h["loss"] for h in res["history"]]
    log(f"  C={spec.C} D={sim.engine.D}: eval loss {loss0!r} -> "
        f"{losses[-1]!r}")
    if not losses[-1] < loss0:
        problems.append(f"eval loss did not decrease: {loss0} -> "
                        f"{losses[-1]}")
    wall = res["telemetry"].wall
    log(f"  smoke timing, not a benchmark metric: first segment "
        f"(compile + round 1) {wall.get('first_segment_s')!r} s, steady "
        f"rounds 2-{ROUNDS} {wall.get('steady_s')!r} s")
    host = spec.host()
    r_host = host.run(max_rounds=ROUNDS, eval_every=ROUNDS)
    compare("device vs host engine", v, np.asarray(host.engine.state.v),
            census(res["telemetry"]), census(r_host["telemetry"]), spec.C,
            problems)
    return sim, res


def phase_logreg_dp(problems):
    import numpy as np
    spec = Logreg(dp=True)
    sim, res = run_device(spec, 1)
    v = check_engine_run(sim, res,
                         want_kernels=TICK_KERNELS + ("cohort_clip_noise",),
                         problems=problems)
    log(f"  operand noise: eval loss {res['history'][0]['loss']!r} -> "
        f"{res['final']['loss']!r} (sigma 8 noise on one-step rounds; "
        f"not required to fall)")
    host = spec.host()
    r_host = host.run(max_rounds=ROUNDS, eval_every=ROUNDS)
    compare("device vs host engine (DP)", v,
            np.asarray(host.engine.state.v), census(res["telemetry"]),
            census(r_host["telemetry"]), spec.C, problems)
    # in-kernel noise: distributional, so the census is compared and
    # the draws go through the chi-square test of tests/test_tick_fused
    sim_k, res_k = run_device(spec, 1, dp_rng="in_kernel")
    check_engine_run(sim_k, res_k,
                     want_kernels=TICK_KERNELS + ("cohort_clip_noise_prng",),
                     problems=problems)
    cen_k, cen = census(res_k["telemetry"]), census(res["telemetry"])
    if cen_k != cen:
        problems.append("in_kernel: integer census differs from the "
                        "operand-noise run")
    else:
        log("  in_kernel: integer census equal to the operand-noise run")
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from test_tick_fused import test_in_kernel_prng_noise_chi_square
    for C, D in ((64, 512), (C_LOGREG, 256)):
        test_in_kernel_prng_noise_chi_square(C, D)
        log(f"  in_kernel: chi-square of the in-kernel normals passed "
            f"(C={C}, D={D})")


def phase_model(problems):
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    import llm_fl_pretrain
    with on_chips(1):
        out = llm_fl_pretrain.main(["--engine", "device"])
    eng = out["sim"].engine
    log(f"  flat adapter: D={eng.D} C={eng.C}, eval loss "
        f"{out['loss0']!r} -> {out['loss1']!r}")
    if not out["loss1"] < out["loss0"]:
        problems.append("model eval loss did not decrease")


def phase_logreg_4chips(problems):
    import numpy as np
    from repro.telemetry.costs import collectives_in
    spec = Logreg(dp=False)
    sim4, res4 = run_device(spec, 4)
    v4 = check_engine_run(sim4, res4, want_kernels=TICK_KERNELS,
                          problems=problems)
    colls = sorted(set(collectives_in(segment_text(sim4))))
    log(f"  client axis sharded over {sim4.engine.mesh.devices.size} "
        f"chips; collectives: {colls}")
    wall = res4["telemetry"].wall
    log(f"  smoke timing, not a benchmark metric: first segment "
        f"{wall.get('first_segment_s')!r} s, steady "
        f"{wall.get('steady_s')!r} s")
    sim1, res1 = run_device(spec, 1)
    compare("4 chips vs 1 chip", v4, np.asarray(sim1.engine.state.v),
            census(res4["telemetry"]), census(res1["telemetry"]), spec.C,
            problems)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run the script "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    log(f"cache: {enable_compile_cache()}")
    log(f"device: {devices[0].device_kind}, {len(devices)} visible, "
        f"using {args.chips}")

    if args.chips == 4:
        phases = [("logreg_4chips", phase_logreg_4chips)]
    else:
        phases = [("logreg", phase_logreg), ("logreg_dp", phase_logreg_dp),
                  ("model", phase_model)]
    failed = []
    for name, fn in phases:
        log(f"phase {name}:")
        problems: list = []
        t0 = time.perf_counter()
        try:
            fn(problems)
        except Exception:  # noqa: BLE001 — reported, then the run fails
            problems.append(traceback.format_exc())
        secs = time.perf_counter() - t0
        if problems:
            failed.append(name)
            for p in problems:
                log(f"  FAIL: {p}")
        log(f"phase {name}: {'FAIL' if problems else 'pass'} "
            f"({secs:.1f} s wall, smoke timing)")
    if failed:
        log(f"chip_smoke: failed phases {failed}")
        return 1
    log(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
