"""The paper's own experiment models: (strongly-)convex logistic regression.

loss(w) = BCE(sigmoid(x·w + b), y) [+ lambda/2 ||w||^2 for strong convexity]
Matches §E.1 equations (32)/(strongly convex J-hat).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_params(d_features: int, key=None, dtype=jnp.float32):
    if key is None:
        key = jax.random.PRNGKey(0)
    w = 0.01 * jax.random.normal(key, (d_features,), jnp.float32)
    return {"w": w.astype(dtype), "b": jnp.zeros((), dtype)}


def predict_logits(params, x):
    return x @ params["w"] + params["b"]


def bce_with_logits(z, y):
    """Numerically stable BCE of labels ``y`` in {0,1} at logits ``z``."""
    return jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))


def per_example_loss(params, x, y, l2: float = 0.0):
    """x: (d,), y: scalar in {0,1}."""
    z = x @ params["w"] + params["b"]
    loss = bce_with_logits(z, y)
    if l2 > 0.0:
        loss = loss + 0.5 * l2 * jnp.sum(jnp.square(params["w"]))
    return loss


def batch_loss(params, xb, yb, l2: float = 0.0):
    z = xb @ params["w"] + params["b"]
    loss = jnp.mean(bce_with_logits(z, yb))
    if l2 > 0.0:
        loss = loss + 0.5 * l2 * jnp.sum(jnp.square(params["w"]))
    return loss


def accuracy(params, xb, yb):
    pred = (predict_logits(params, xb) > 0).astype(jnp.float32)
    return jnp.mean((pred == yb).astype(jnp.float32))
