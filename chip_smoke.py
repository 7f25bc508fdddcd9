#!/usr/bin/env python3
"""Smoke run of the device engine on a TPU: the quickest proof that the
repo's main path compiles and runs on the chip.

    python chip_smoke.py             # one chip: phases logreg, logreg_dp,
                                     # client_block, model, granite_lora
    python chip_smoke.py --chips 4   # phase logreg with the client axis
                                     # sharded over four chips, against
                                     # the same run on one chip

Every engine phase goes through
``make_simulator(FLConfig(engine="device"), ...)`` and checks what comes
out.  On a TPU the host engine's block is the same client-block kernel,
so phase client_block holds the compiled kernel to its jnp reference
directly, on masked multi-step blocks.  Phase granite_lora holds the
compiled LoRA block of granite-4.0-h-micro at published widths to the
benchmark's plain float32 reference (``bench/references``).  Earlier
lines report each phase; the last
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
LOGREG_KERNELS = TICK_KERNELS + ("client_block_sgd",)


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
    """Compiled HLO of the segment the engine ran (no second compile)."""
    return sim.engine.segment_hlo()


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
    v = check_engine_run(sim, res, want_kernels=LOGREG_KERNELS,
                         problems=problems)
    losses = [h["loss"] for h in res["history"]]
    log(f"  C={spec.C} D={sim.engine.D}: eval loss {loss0!r} -> "
        f"{losses[-1]!r}")
    if not losses[-1] < loss0:
        problems.append(f"eval loss did not decrease: {loss0} -> "
                        f"{losses[-1]}")
    wall = res["telemetry"].wall
    log(f"  smoke timing, not a benchmark metric: compile "
        f"{wall.get('compile_s')!r} s, first segment (round 1) "
        f"{wall.get('first_segment_s')!r} s, steady rounds 2-{ROUNDS} "
        f"{wall.get('steady_s')!r} s")
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
                         want_kernels=LOGREG_KERNELS + ("cohort_clip_noise",),
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
                     want_kernels=LOGREG_KERNELS + (
                         "cohort_clip_noise_prng",),
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


def _block_case(C, block, seed):
    """A masked block of the logreg at D = 785: the data set, client
    state with ``-0.0`` lanes, sample rows, step counts from 0 to the
    whole block, step sizes."""
    import jax
    import jax.numpy as jnp
    from repro.data import make_binary_dataset
    X, y = make_binary_dataset(N_EXAMPLES, N_FEATURES, seed=seed)
    X, y = jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    D = N_FEATURES + 1
    w = (0.1 * jax.random.normal(ks[0], (C, D))).at[::7, ::5].set(-0.0)
    U = (0.1 * jax.random.normal(ks[1], (C, D))).at[::3, ::4].set(-0.0)
    idx = jax.random.randint(ks[2], (C, block), 0, N_EXAMPLES)
    n = jax.random.randint(ks[3], (C,), 0, block + 1)
    n = n.at[::9].set(0).at[1::9].set(block)
    eta = jax.random.uniform(ks[4], (C,), jnp.float32, 0.05, 0.5)
    return X, y, w, U, idx, n, eta


def check_client_block(C, block, problems, *, mesh=None):
    """The compiled client-block kernel against its jnp reference
    (``kernels/client_block/ref.py``) on the chip, on a masked block of
    ``block`` steps for C clients, clip off and on (Fig. 1b's 0.1); under
    ``mesh``, per client shard.  The row gather and the masks must be
    exact: a client that takes no step keeps its state's values.  The
    logit dot and the clip norm add over D in the kernel's order; each
    step's gradient is held to the reduction-order bound 2 * D * 2^-24
    of its size, compounded over the block (the bound of
    tests/test_client_block.py)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.client_block import client_block_ref, client_block_sgd
    from repro.kernels.client_block.ops import steps_per_gather
    X, y, w, U, idx, n, eta = _block_case(C, block, SEED + C)
    D = w.shape[1]
    shards = 1 if mesh is None else mesh.devices.size
    X_aug = jnp.concatenate([X, y[:, None]], axis=1)
    idle = np.asarray(n) == 0
    for clip in (0.0, 0.1):
        kw = dict(l2=1.0 / N_EXAMPLES, clip=clip)
        ker = client_block_sgd(w, U, idx.T, n, eta, X_aug, mesh=mesh, **kw)
        ref = client_block_ref(w, U, idx, n, eta, X, y, **kw)
        errs = []
        for name, k, r, old in zip("wU", ker, ref, (w, U)):
            k, r, old = np.asarray(k), np.asarray(r), np.asarray(old)
            tol = float(block * 2 * D * 2.0 ** -24 * np.abs(r - old).max()
                        + block * 2.0 ** -24 * max(1.0, np.abs(r).max()))
            err = float(np.abs(k - r).max())
            errs.append(f"{name} {err!r} (bound {tol!r})")
            if not err <= tol:
                problems.append(f"client block C={C} clip={clip}: {name} "
                                f"differs by {err} > {tol}")
            if not (np.array_equal(k[idle], r[idle])
                    and np.array_equal(k[idle], old[idle])):
                problems.append(f"client block C={C} clip={clip}: an idle "
                                f"client's {name} changed")
        log(f"  C={C} over {shards} chip(s), block={block} (gathers of "
            f"{steps_per_gather(block, C // shards, D)} steps) clip={clip}:"
            f" kernel vs reference max |diff| {', '.join(errs)}; "
            f"{int(idle.sum())} idle clients equal")


def phase_client_block(problems):
    """Masked 64-step blocks at C_LOGREG clients (one gather) and at
    2^14 (the rows gathered in four chunks of 16 steps)."""
    with on_chips(1):
        for C in (C_LOGREG, 1 << 14):
            check_client_block(C, 64, problems)


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


#: granite_lora: the compiled block's gradients may differ from the f32
#: reference's by this share of the reference's largest: the block takes
#: bfloat16 matmul inputs (the configuration's precision), and a random
#: ten-layer period's gradients move by a few percent under that rounding
#: (2% at hidden size 256 on the CPU); a wrong mixer, scale or residual
#: moves them by order one
GRANITE_GRAD_TOL = 0.1
GRANITE_SEED = 2 ** 31 + 15


def phase_granite_lora(problems):
    """The compiled LoRA block at published widths, 2 clients x 1 step
    from the seed's adapters, against the reference's gradients (and the
    reference one precision step down, for scale)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    from yardstick.measure import sized
    from yardstick.spec import Cell, model_of, reference_of

    cell = Cell("granite_h_micro_lora_fedavg")
    config, traffic = sized(cell, {"clients": 2})
    model, ref = model_of(config), reference_of(config, traffic)
    with on_chips(1):
        inputs = model.inputs(GRANITE_SEED, config)
        _, ctask = model.build(config, inputs, GRANITE_SEED)
        v0 = jnp.asarray(model.initial_vector(inputs))
        C = ctask.C
        zi = jnp.zeros((C,), jnp.int32)
        block = jax.jit(ctask.block_body(1))
        t0 = time.perf_counter()
        _, U = block(jnp.tile(v0[None], (C, 1)), jnp.zeros((C, ctask.D)),
                     zi, zi, zi + 1, jnp.zeros((C,)), ctask.operands)
        U = np.asarray(U)
        log(f"  block compiled and run in {time.perf_counter() - t0:.1f} s "
            f"(smoke timing); D={ctask.D}")
        base = jax.random.PRNGKey(GRANITE_SEED)
        for cl in range(C):
            key = jax.random.fold_in(jax.random.fold_in(
                jax.random.fold_in(base, cl), 0), 0)
            g, g_low = (np.asarray(ref.grad(v0, inputs["base"], key,
                                            st=ref.statics(config, low)))
                        for low in (False, True))
            scale = float(np.abs(g).max())
            err = float(np.abs(U[cl] - g).max()) / scale
            low_err = float(np.abs(g_low - g).max()) / scale
            log(f"  client {cl}: max |U - ref| / max |ref| {err!r} "
                f"(tolerance {GRANITE_GRAD_TOL}); the control's "
                f"{low_err!r}")
            if not err <= GRANITE_GRAD_TOL:
                problems.append(f"granite_lora client {cl}: gradients "
                                f"differ by {err} > {GRANITE_GRAD_TOL}")


def phase_logreg_4chips(problems):
    import numpy as np
    from repro.telemetry.costs import collectives_in
    spec = Logreg(dp=False)
    sim4, res4 = run_device(spec, 4)
    v4 = check_engine_run(sim4, res4, want_kernels=LOGREG_KERNELS,
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
    # the engine's blocks here take one step: the kernel per shard on
    # masked 64-step blocks, against the reference
    check_client_block(1 << 14, 64, problems, mesh=sim4.engine.mesh)


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
                  ("client_block", phase_client_block),
                  ("model", phase_model),
                  ("granite_lora", phase_granite_lora)]
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
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices[:args.chips])
        log(f"  device memory peak so far: {peak} bytes (the most of any "
            f"chip in use; peak_bytes_in_use)")
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
