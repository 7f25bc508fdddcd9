"""Feed-forward blocks: gated (SwiGLU/GeGLU) and plain GELU MLPs."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import expand_rank, fan_in_init, gated_act, matmul


def init_mlp(cfg, key, dtype, *, n_layers=None, d_ff=None):
    L = n_layers if n_layers is not None else cfg.n_layers
    ff = d_ff if d_ff is not None else cfg.d_ff
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    if cfg.fused_gate_up:
        p = {
            "wi": fan_in_init(ks[0], (L, d, 2 * ff), dtype),
            "wd": fan_in_init(ks[2], (L, ff, d), dtype),
        }
    elif cfg.activation in ("silu", "geglu"):
        p = {
            "wg": fan_in_init(ks[0], (L, d, ff), dtype),
            "wu": fan_in_init(ks[1], (L, d, ff), dtype),
            "wd": fan_in_init(ks[2], (L, ff, d), dtype),
        }
    else:  # plain gelu (whisper / grok expert style handled in moe)
        p = {
            "wu": fan_in_init(ks[0], (L, d, ff), dtype),
            "wd": fan_in_init(ks[1], (L, ff, d), dtype),
        }
        if cfg.mlp_bias:
            p["bu"] = jnp.zeros((L, ff), dtype)
            p["bd"] = jnp.zeros((L, d), dtype)
    return p


def apply_mlp(cfg, lp, x, *, dense=matmul):
    """lp holds one layer's slices (no leading L axis).  ``dense(name,
    x, w)`` computes each projection: ``wi`` (gate | up, fused), or
    ``wg`` and ``wu``; then ``wd``."""
    if "wi" in lp:
        gate, up = jnp.split(dense("wi", x, lp["wi"]), 2, axis=-1)
        h = gated_act(cfg.activation, gate, up)
        return dense("wd", h, lp["wd"])
    if "wg" in lp:
        gate = jnp.einsum("bsd,df->bsf", x, lp["wg"])
        up = jnp.einsum("bsd,df->bsf", x, lp["wu"])
        h = gated_act(cfg.activation, gate, up)
    else:
        h = jnp.einsum("bsd,df->bsf", x, lp["wu"])
        if "bu" in lp:
            h = h + expand_rank(lp["bu"], h.ndim)
        h = jax.nn.gelu(h, approximate=True)
    out = jnp.einsum("bsf,fd->bsd", h, lp["wd"])
    if "bd" in lp:
        out = out + expand_rank(lp["bd"], out.ndim)
    return out
