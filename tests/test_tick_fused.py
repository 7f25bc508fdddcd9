"""Fused device-tick kernels (repro.kernels.tick_fused): ref-vs-kernel
fire tests on the CPU interpreter (padded and unpadded C / D), the
empty-bucket ``-0.0`` guarded-add hazard, the ``dp_rng`` knob, the
in-kernel-PRNG DP distribution (TPU only), and tick coalescing
(``fuse_ticks``) staying bitwise with the unfused loop."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cohort import DeviceCohortSimulator
from repro.core import LogRegTask
from repro.data import make_binary_dataset
from repro.kernels.tick_fused import (bucket_apply, tick_deliver,
                                      tick_scatter)


def _task(n=300, d=12, seed=9, sample_seed=21, **kw):
    X, y = make_binary_dataset(n, d, seed=seed, noise=0.3)
    return LogRegTask(X, y, l2=1.0 / n, sample_seed=sample_seed, **kw)


def _rand(key, *shape):
    return jax.random.normal(key, shape, jnp.float32)


# --- ref vs interpret-kernel fire tests -------------------------------------

@pytest.mark.parametrize("A", [1, 4])
@pytest.mark.parametrize("D", [8, 10])          # exact vs padded lanes
@pytest.mark.parametrize("flag", [False, True])
def test_bucket_apply_kernel_matches_ref(A, D, flag):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    v, rows = _rand(ks[0], D), _rand(ks[1], A, D)
    dec = jax.random.uniform(ks[2], (A,), jnp.float32)
    ref = bucket_apply(v, rows, dec, flag, use_kernel=False)
    ker = bucket_apply(v, rows, dec, flag, use_kernel=True,
                       interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


@pytest.mark.parametrize("C", [5, 8])           # padded vs exact clients
@pytest.mark.parametrize("D", [8, 10])
def test_tick_deliver_kernel_matches_ref(C, D):
    B = 4
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    w, U, bc_v = _rand(ks[0], C, D), _rand(ks[1], C, D), _rand(ks[2], B, D)
    best = jax.random.randint(ks[3], (C,), 0, B)
    take = jnp.asarray([True, False, True, True, False][:C] + [True] * 0)
    take = jnp.resize(take, (C,))
    eta = jnp.linspace(0.05, 0.1, C, dtype=jnp.float32)
    ref = tick_deliver(w, U, bc_v, best, take, eta, use_kernel=False)
    ker = tick_deliver(w, U, bc_v, best, take, eta, use_kernel=True,
                       interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


@pytest.mark.parametrize("C", [5, 8])
@pytest.mark.parametrize("D", [8, 10])
@pytest.mark.parametrize("dp_on", [False, True])
def test_tick_scatter_kernel_matches_ref(C, D, dp_on):
    G = 3
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    sent, w, U = (_rand(k, C, D) for k in ks[:3])
    upd = _rand(ks[3], G, D)
    wgt = jax.random.uniform(ks[4], (G, C), jnp.float32)
    # zero out one group's weights entirely (its guarded add must skip)
    wgt = wgt.at[1].set(0.0)
    any_g = jnp.asarray([True, False, True])
    done = jnp.asarray(([True, False] * C)[:C])
    eta = jnp.linspace(0.05, 0.1, C, dtype=jnp.float32)
    ref = tick_scatter(sent, w, U, upd, wgt, any_g, done, eta,
                       dp_on=dp_on, use_kernel=False)
    ker = tick_scatter(sent, w, U, upd, wgt, any_g, done, eta,
                       dp_on=dp_on, use_kernel=True, interpret=True)
    for r, k in zip(ref, ker):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(k))


def _sum_bound(terms):
    """Reduction-order bound of an f32 sum of n terms (README parity
    table): 2 * n * 2^-24 * sum |terms|, per output element."""
    a = np.abs(np.asarray(terms, np.float64))
    return 2 * a.shape[0] * 2.0 ** -24 * a.sum(axis=0)


def _assert_one_rounding(got, a, bc):
    """``got`` is ``a + bc`` in f32, with the product ``bc`` rounded or
    fused into a multiply-add: within 2 * 2^-24 * (|a| + |bc|) of the
    float64 value."""
    a, bc = np.asarray(a, np.float64), np.asarray(bc, np.float64)
    np.testing.assert_array_less(np.abs(np.asarray(got) - (a + bc)),
                                 2.0 ** -23 * (np.abs(a) + np.abs(bc))
                                 + 1e-30)


def test_client_tiled_kernels_within_reduction_order_bound():
    """The compiled path tiles C; here four C tiles run in interpret
    mode.  Selections stay exact; elementwise multiply-adds are within
    one rounding of the product (a backend may fuse them), and the sums
    over clients add tile by tile within their reduction-order bound of
    the float64 sum."""
    from repro.kernels.cohort_dp.kernel import cohort_clip_noise_kernel
    from repro.kernels.tick_fused.kernel import (tick_deliver_kernel,
                                                 tick_scatter_kernel)
    C, D, G, B, tile = 32, 16, 3, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    sent, w, U, noise = (np.asarray(_rand(k, C, D)) for k in ks[:4])
    wgt = np.asarray(jax.random.uniform(ks[4], (C, G), jnp.float32))
    done = np.arange(C) % 3 != 0
    eta = np.linspace(0.05, 0.1, C, dtype=np.float32)
    col = lambda x, dt: jnp.asarray(x, dt)[:, None]            # noqa: E731
    kw = dict(c_block=tile, d_block=8, interpret=True)
    on = done[:, None]

    w_new, u_new, vec = tick_scatter_kernel(
        sent, w, U, wgt, col(done, jnp.int32), col(eta, jnp.float32),
        dp_on=True, **kw)
    np.testing.assert_array_equal(np.asarray(u_new),
                                  np.where(on, 0.0, sent))
    np.testing.assert_array_equal(np.asarray(w_new)[~done], w[~done])
    _assert_one_rounding(np.asarray(w_new)[done], w[done],
                         (eta[:, None] * (sent - U))[done])
    for g in range(G):
        terms = sent * wgt[:, g:g + 1]
        np.testing.assert_array_less(
            np.abs(np.asarray(vec[g]) - terms.astype(np.float64).sum(0)),
            _sum_bound(terms) + 1e-30)

    best = np.asarray(jax.random.randint(ks[5], (C,), 0, B))
    bc_v = np.asarray(_rand(ks[6], B, D))
    got = np.asarray(tick_deliver_kernel(
        w, U, bc_v, col(best, jnp.int32), col(done, jnp.int32),
        col(eta, jnp.float32), **kw))
    np.testing.assert_array_equal(got[~done], w[~done])
    _assert_one_rounding(got[done], bc_v[best][done],
                         -(eta[:, None] * U)[done])

    out, agg = cohort_clip_noise_kernel(
        sent, noise, col(eta, jnp.float32), col(done, jnp.float32),
        clip=0.0, noise_scale=0.5, **kw)
    out = np.asarray(out)
    np.testing.assert_array_equal(out[~done], sent[~done])
    _assert_one_rounding(out[done], sent[done], 0.5 * noise[done])
    terms = out * eta[:, None]
    np.testing.assert_array_less(
        np.abs(np.asarray(agg[0]) - terms.astype(np.float64).sum(0)),
        _sum_bound(terms) + 1e-30)


def test_empty_bucket_negative_zero_preserved():
    """Guarded adds: a flagged-off bucket apply and an empty scatter
    group must pass ``-0.0`` through bit-for-bit (the hazard that makes
    ``where(any(in_l), cur + vec, cur)`` mandatory — an unconditional
    ``+ 0.0`` would flip the sign bit and break host-vs-device parity).
    """
    D = 8
    neg = jnp.full((D,), -0.0, jnp.float32)
    for path in (dict(use_kernel=False),
                 dict(use_kernel=True, interpret=True)):
        out = bucket_apply(neg, jnp.ones((2, D), jnp.float32),
                           jnp.ones((2,), jnp.float32), False, **path)
        assert np.signbit(np.asarray(out)).all(), path
        w_new, u_new, upd_new = tick_scatter(
            jnp.zeros((4, D), jnp.float32), neg[None, :] * jnp.ones((4, 1)),
            jnp.zeros((4, D), jnp.float32), neg[None, :].repeat(2, axis=0),
            jnp.zeros((2, 4), jnp.float32), jnp.asarray([False, False]),
            jnp.zeros((4,), bool), jnp.full((4,), 0.1, jnp.float32),
            dp_on=False, **path)
        assert np.signbit(np.asarray(upd_new)).all(), path
        assert np.signbit(np.asarray(w_new)).all(), path
    # the A == 1 static branch: rows[0] * dec keeps -0.0 where a
    # size-1 jnp.sum would have flipped it to +0.0
    v = jnp.full((D,), -0.0, jnp.float32)
    row = jnp.full((1, D), -0.0, jnp.float32)
    ref = bucket_apply(v, row, jnp.ones((1,), jnp.float32), True,
                       use_kernel=False)
    ker = bucket_apply(v, row, jnp.ones((1,), jnp.float32), True,
                       use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.signbit(np.asarray(ref)),
                                  np.signbit(np.asarray(ker)))


# --- dp_rng knob ------------------------------------------------------------

def test_dp_rng_knob_validation():
    task = _task(dp_clip=0.1, dp_sigma=1.0)
    kw = dict(n_clients=4, sizes_per_client=[2], round_stepsizes=[0.1],
              d=1, seed=0, block=4)
    with pytest.raises(ValueError, match="dp_rng"):
        DeviceCohortSimulator(task, dp_rng="nope", **kw)
    if jax.default_backend() != "tpu":
        with pytest.raises(ValueError, match="TPU"):
            DeviceCohortSimulator(task, dp_rng="in_kernel", **kw)
    else:
        with pytest.raises(ValueError, match="use_dp_kernel"):
            DeviceCohortSimulator(task, dp_rng="in_kernel",
                                  use_dp_kernel=False, **kw)


@pytest.mark.parametrize("C,D", [(64, 512), (4096, 256)])
def test_in_kernel_prng_noise_chi_square(C, D):
    """dp_rng='in_kernel' draws standard normals inside the kernel —
    distributionally equivalent to the operand path (chi-square over
    normal-quantile bins), never bitwise.  At C=4096 the draw spans
    several client tiles, each seeded on its own.  TPU only by
    contract."""
    if jax.default_backend() != "tpu":
        pytest.skip("in-kernel PRNG path needs a TPU backend "
                    "(pltpu.prng_random_bits has no CPU/GPU lowering)")
    from repro.kernels.cohort_dp.ops import cohort_clip_noise
    u = jnp.zeros((C, D), jnp.float32)
    out, _ = cohort_clip_noise(
        u, jax.random.PRNGKey(5), jnp.ones((C,), jnp.float32),
        jnp.ones((C,), jnp.float32), clip=0.0, noise_scale=1.0,
        use_kernel=True, in_kernel_rng=True)
    out = np.asarray(out)
    # no two client tiles (nor the halves of one) repeat a stream
    half = C // 2
    assert not np.array_equal(out[:half], out[half:])
    s = out.ravel()
    assert abs(s.mean()) < 0.02 and abs(s.std() - 1.0) < 0.02
    edges = np.array([-2.0, -1.0, -0.5, 0.0, 0.5, 1.0, 2.0])
    cdf = np.vectorize(
        lambda x: 0.5 * (1.0 + math.erf(x / math.sqrt(2.0))))
    probs = np.diff(np.concatenate([[0.0], cdf(edges), [1.0]]))
    counts, _ = np.histogram(s, bins=np.concatenate(
        [[-np.inf], edges, [np.inf]]))
    expected = probs * s.size
    chi2 = float(((counts - expected) ** 2 / expected).sum())
    df = len(probs) - 1
    assert chi2 < df + 5.0 * math.sqrt(2.0 * df), (chi2, counts)


# --- tick coalescing --------------------------------------------------------

def test_fuse_ticks_bitwise_and_iter_relations():
    """fuse_ticks=True runs the SAME tick sequence as the unfused loop
    (bitwise state, identical op census) in fewer while_loop iterations;
    the ``iters`` census obeys block_iters <= loop_iters <= ticks <=
    2 * loop_iters, with the unfused loop pinning one tick (and one
    block tick) per iteration."""
    task = _task()
    kw = dict(n_clients=8, sizes_per_client=[1] * 8,
              round_stepsizes=[0.1] * 8, d=1, seed=0, block=4)
    sim_off = DeviceCohortSimulator(task, fuse_ticks=False, **kw)
    res_off = sim_off.run(max_rounds=8, eval_every=8)
    sim_on = DeviceCohortSimulator(task, fuse_ticks=True, **kw)
    res_on = sim_on.run(max_rounds=8, eval_every=8)
    np.testing.assert_array_equal(np.asarray(res_off["model"]["w"]),
                                  np.asarray(res_on["model"]["w"]))
    assert float(res_off["model"]["b"]) == float(res_on["model"]["b"])
    tel_off, tel_on = res_off["telemetry"], res_on["telemetry"]
    assert dict(tel_off.ops) == dict(tel_on.ops)
    assert tel_off.ticks == tel_on.ticks
    li_off, bi_off = sim_off.engine.fused_iters
    li_on, bi_on = sim_on.engine.fused_iters
    block_ticks = dict(tel_on.ops)["block_ticks"]
    # unfused: one tick per iteration, block attribution is exact
    assert li_off == tel_off.ticks and bi_off == block_ticks
    # fused: every iteration runs 1-2 ticks and holds <= 1 block tick
    assert bi_on <= li_on <= tel_on.ticks <= 2 * li_on
    assert block_ticks >= bi_on
    # coalescing actually fires on the FedSGD-shaped workload (half of
    # its ticks are overhead-only, so they ride along)
    assert li_on < li_off


# --- client axis sharded over several devices --------------------------------

_SHARDED_SCRIPT = r"""
import json, sys
import jax, numpy as np
import repro.kernels.tick_fused.ops as tick_ops
# the fused kernels (interpret mode) in place of the CPU reference, so
# their shard_map path runs
tick_ops._resolve = lambda use_kernel, interpret: (True, True)
from repro.cohort import DeviceCohortSimulator
from repro.core import LogRegTask
from repro.data import make_binary_dataset
X, y = make_binary_dataset(512, 20, seed=0)
out = {}
for dp in (False, True):
    task = LogRegTask(X, y, l2=1e-3, sample_seed=1,
                      **(dict(dp_clip=0.1, dp_sigma=8.0) if dp else {}))
    kw = dict(n_clients=64, sizes_per_client=[1] * 4,
              round_stepsizes=[0.1] * 4, d=3 if dp else 1, seed=0,
              block=1, scenario="mobile_diurnal" if dp else "uniform")
    runs = []
    for n in (4, 1):
        if n == 1:
            with jax.default_device(jax.devices()[0]):
                sim = DeviceCohortSimulator(task, **kw)
        else:
            sim = DeviceCohortSimulator(task, **kw)
        res = sim.run(max_rounds=4, eval_every=2)
        tel = res["telemetry"]
        runs.append(dict(
            devices=int(sim.engine.mesh.devices.size),
            census=[int(tel.messages), int(tel.broadcasts),
                    {k: int(v) for k, v in tel.ops.items()},
                    [int(x) for x in tel.staleness_hist]],
            v=np.asarray(sim.engine.state.v).tolist()))
    out[str(dp)] = runs
print(json.dumps(out))
"""


def test_sharded_client_axis_matches_one_device():
    """Four virtual CPU devices: the engine shards C, the fused kernels
    run per shard under shard_map and the ring sums are psum'ed.  The
    integer census equals the one-device run's; the model differs only
    by the add order of the shards' partial sums.  (A child process:
    the device count is fixed when JAX starts.)"""
    import json
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for dp, (four, one) in out.items():
        assert (four["devices"], one["devices"]) == (4, 1), dp
        assert four["census"] == one["census"], dp
        v4, v1 = np.asarray(four["v"]), np.asarray(one["v"])
        # 4 rounds, each a sum over 64 clients re-associated across 4
        # shards: 4 * 2 * 64 * 2^-24 of the model's magnitude
        tol = 4 * 2 * 64 * 2.0 ** -24 * max(1.0, np.abs(v1).max())
        assert np.abs(v4 - v1).max() <= tol, (dp, np.abs(v4 - v1).max())
