"""Inputs of a run, made on the device from ``--seed`` in one jitted call.

The data follow the arithmetic of ``repro.data.make_binary_dataset``
(a random separating direction, standard-normal rows, labels from the
margin plus Gaussian label noise), drawn with ``jax.random`` so that a
60,000 x 784 set is made on the chip and not on the host.  The initial
model is the logreg initialisation (0.01 standard normal weights, zero
bias), also from the seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LABEL_NOISE = 0.5


def seed32(seed: int) -> int:
    """The run's seed as JAX and numpy keys take it: ``--seed`` may be
    any whole number up to a little over 2**31, and a key made from a
    Python int keeps only its low 32 bits."""
    return int(seed) % (2 ** 31)


@functools.partial(jax.jit, static_argnames=("n", "d"))
def _make(key, *, n: int, d: int):
    kw, kx, kn, ki = jax.random.split(key, 4)
    w_true = jax.random.normal(kw, (d,), jnp.float32)
    X = jax.random.normal(kx, (n, d), jnp.float32)
    margin = jnp.dot(X, w_true,
                     precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(d)
    y = (margin + LABEL_NOISE * jax.random.normal(kn, (n,), jnp.float32)
         > 0).astype(jnp.float32)
    w0 = 0.01 * jax.random.normal(ki, (d,), jnp.float32)
    return X, y, w0


def make_inputs(seed: int, n_examples: int, n_features: int):
    """-> (X [n, d], y [n], w0 [d]) on the default device."""
    X, y, w0 = _make(jax.random.PRNGKey(seed32(seed)), n=int(n_examples),
                     d=int(n_features))
    return X, y, w0
