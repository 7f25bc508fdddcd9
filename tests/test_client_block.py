"""Fused local-SGD client block (repro.kernels.client_block): the Pallas
kernel on the CPU interpreter against the jnp reference, over padded
and unpadded fleets, one and several client tiles, the model widths
the engine runs, clip on and off, heterogeneous step counts with masked
steps, and ``-0.0`` lanes in ``w`` and ``U``; and the cohort task's
backend dispatch.

Contract: the row gather and the masks are exact, so a client that
takes no step comes back with the reference's values (by value: the
sign of a masked step's ``act * g = ±0.0`` on a ``-0.0`` lane depends
on how the backend compiles the multiply by the mask — XLA's CPU
backend turns it into a select on the reference's bias lane); the
logit dot and the clip norm add over D in the kernel's order, so each
step's gradient may differ from the reference's by the reduction-order
bound 2·D·2⁻²⁴ of its size, which compounds over the block's steps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cohort.tasks import CohortLogRegTask
from repro.core import LogRegTask
from repro.data import make_binary_dataset
from repro.kernels.client_block import client_block_ref, client_block_sgd
from repro.kernels.client_block import ops
from repro.kernels.client_block.ops import client_tile, steps_per_gather

N_DATA = 257


def _case(C, D, block, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    X = 0.5 * jax.random.normal(ks[0], (N_DATA, D - 1), jnp.float32)
    y = (jax.random.uniform(ks[1], (N_DATA,)) > 0.5).astype(jnp.float32)
    w = 0.1 * jax.random.normal(ks[2], (C, D), jnp.float32)
    U = 0.1 * jax.random.normal(ks[3], (C, D), jnp.float32)
    # signed zeros: a masked step adds act * g = ±0.0 to them
    w = w.at[::7, ::5].set(-0.0)
    U = U.at[::3, ::4].set(-0.0)
    idx = jax.random.randint(ks[4], (C, block), 0, N_DATA)
    # every count from 0 (all steps masked) to the whole block
    n = jax.random.randint(ks[5], (C,), 0, block + 1)
    n = n.at[::9].set(0).at[1::9].set(block)
    eta = jax.random.uniform(ks[6], (C,), jnp.float32, 0.05, 0.5)
    return X, y, w, U, idx, n, eta


def _kernel(X, y, w, U, idx, n, eta, **kw):
    X_aug = jnp.concatenate([X, y[:, None]], axis=1)
    return client_block_sgd(w, U, idx.T, n, eta, X_aug, interpret=True, **kw)


def _tol(new, old, block, D):
    """Per-step reduction-order bound on the gradient, relative to the
    block's whole change of the state, over ``block`` steps; plus one
    rounding of the state a step."""
    new, old = np.asarray(new), np.asarray(old)
    rel = block * 2 * D * 2.0 ** -24
    return rel * np.abs(new - old).max() + block * 2.0 ** -24 * max(
        1.0, np.abs(new).max())


# C = 64: one tile; 100: padded to the client tile; 4096: several tiles
SHAPES = [(64, 33, 1), (64, 785, 64), (100, 33, 64), (100, 785, 1),
          (4096, 33, 64), (4096, 785, 1)]


@pytest.mark.parametrize("clip", [0.0, 0.1])
@pytest.mark.parametrize("C,D,block", SHAPES)
def test_kernel_matches_ref(C, D, block, clip):
    X, y, w, U, idx, n, eta = _case(C, D, block)
    kw = dict(l2=1.0 / N_DATA, clip=clip)
    wk, uk = _kernel(X, y, w, U, idx, n, eta, **kw)
    wr, ur = client_block_ref(w, U, idx, n, eta, X, y, **kw)
    assert wk.shape == wr.shape == (C, D) and uk.shape == ur.shape
    assert np.abs(np.asarray(wk - wr)).max() <= _tol(wr, w, block, D)
    assert np.abs(np.asarray(uk - ur)).max() <= _tol(ur, U, block, D)
    # a client that takes no step keeps its state, -0.0 lanes included
    idle = np.asarray(n) == 0
    for k, r, old in ((wk, wr, w), (uk, ur, U)):
        np.testing.assert_array_equal(np.asarray(k)[idle],
                                      np.asarray(r)[idle])
        np.testing.assert_array_equal(np.asarray(k)[idle],
                                      np.asarray(old)[idle])


def test_several_client_tiles_where_asked():
    assert client_tile(4096, 785) < 4096 and client_tile(4096, 33) < 4096
    assert client_tile(64, 785) == 64 and client_tile(100, 33) == 104


@pytest.mark.parametrize("C,D,block,k", [
    (16384, 785, 64, 16), (262144, 785, 1, 1), (131072, 785, 64, 2),
    (1048576, 785, 1, 1), (4096, 785, 64, 64), (64, 33, 1, 1),
    (16384, 785, 48, 16), (16384, 785, 40, 10)])
def test_gathered_rows_stay_within_their_budget(C, D, block, k):
    """The block gathers its rows in chunks of k steps: the largest
    divisor of the block whose rows fit ROW_SLAB_BYTES, at least one."""
    assert steps_per_gather(block, C, D) == k
    assert block % k == 0
    step_bytes = C * 896 * 4 if D == 785 else C * 128 * 4
    assert k == 1 or k * step_bytes <= ops.ROW_SLAB_BYTES


@pytest.mark.parametrize("block,steps_fit,clip", [(16, 3, 0.0), (12, 5, 0.1),
                                                  (9, 1, 0.1)])
def test_chunked_block_matches_one_gather(monkeypatch, block, steps_fit,
                                          clip):
    """A block gathered in chunks of steps (2 of 16, 4 of 12, 1 of 9)
    gives the one-gather kernel's values bit for bit, and the
    reference's within the bound: a chunk's step j is the block's step
    s*k + j, masked by the same count."""
    C, D = 64, 33
    X, y, w, U, idx, n, eta = _case(C, D, block, seed=4)
    kw = dict(l2=1.0 / N_DATA, clip=clip)
    client_block_sgd.clear_cache()
    whole = _kernel(X, y, w, U, idx, n, eta, **kw)
    monkeypatch.setattr(ops, "ROW_SLAB_BYTES", steps_fit * C * 128 * 4)
    assert steps_per_gather(block, C, D) < block
    client_block_sgd.clear_cache()
    try:
        chunked = _kernel(X, y, w, U, idx, n, eta, **kw)
    finally:
        client_block_sgd.clear_cache()
    ref = client_block_ref(w, U, idx, n, eta, X, y, **kw)
    for c, a, r, old in zip(chunked, whole, ref, (w, U)):
        np.testing.assert_array_equal(np.asarray(c).view(np.int32),
                                      np.asarray(a).view(np.int32))
        assert np.abs(np.asarray(c - r)).max() <= _tol(r, old, block, D)


def test_kernel_reads_each_steps_own_row():
    """Rows taken one step late (step j reads step j+1's sample) move the
    result far past the bound."""
    C, D, block = 64, 33, 8
    X, y, w, U, idx, n, eta = _case(C, D, block, seed=3)
    n = jnp.full((C,), block, jnp.int32)
    kw = dict(l2=0.0, clip=0.0)
    wr, _ = client_block_ref(w, U, idx, n, eta, X, y, **kw)
    wk, _ = _kernel(X, y, w, U, jnp.roll(idx, -1, axis=1), n, eta, **kw)
    assert np.abs(np.asarray(wk - wr)).max() > 100 * _tol(wr, w, block, D)


def _cohort_task(n_clients=48, d=12, clip=0.0):
    X, y = make_binary_dataset(300, d, seed=9, noise=0.3)
    task = LogRegTask(X, y, l2=1.0 / 300, sample_seed=21, dp_clip=clip)
    return CohortLogRegTask(task, n_clients, seed=0)


@pytest.mark.parametrize("clip", [0.0, 0.1])
def test_cpu_dispatch_is_the_reference(clip):
    """On CPU the cohort task's default block is the jnp reference, bit
    for bit; the kernel path through the same task (its ``X_aug``, its
    step-major indices) agrees with it within the bound."""
    ct = _cohort_task(clip=clip)
    C, D, block = ct.C, ct.D, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    w = 0.1 * jax.random.normal(ks[0], (C, D), jnp.float32)
    U = jnp.zeros((C, D), jnp.float32)
    i = jax.random.randint(ks[1], (C,), 0, 5)
    h = jax.random.randint(ks[2], (C,), 0, 7)
    n = jnp.arange(C, dtype=jnp.int32) % (block + 1)
    eta = jnp.full((C,), 0.1, jnp.float32)
    default = jax.jit(ct.block_body(block))(w, U, i, h, n, eta)
    ref = jax.jit(ct.block_body(block, use_kernel=False))(w, U, i, h, n, eta)
    ker = jax.jit(ct.block_body(block, use_kernel=True))(w, U, i, h, n, eta)
    for a, b, k, old in zip(default, ref, ker, (w, U)):
        np.testing.assert_array_equal(np.asarray(a).view(np.int32),
                                      np.asarray(b).view(np.int32))
        assert np.abs(np.asarray(k - b)).max() <= _tol(b, old, block, D)
    X_aug = np.asarray(ct.X_aug)
    np.testing.assert_array_equal(X_aug[:, :-1], np.asarray(ct.task.X))
    np.testing.assert_array_equal(X_aug[:, -1], np.asarray(ct.task.y))
