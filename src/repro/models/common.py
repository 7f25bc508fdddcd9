"""Shared neural-net building blocks (pure JAX, no flax).

Parameters are plain nested dicts of jnp arrays.  Per-layer parameters are
*stacked* on a leading layer axis so the decoder runs as a single
``jax.lax.scan`` — this keeps HLO size O(1) in depth, which matters when
compiling 64-layer models in the multi-pod dry-run.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def normal_init(key, shape, dtype, stddev: float = 0.02):
    return (stddev * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def fan_in_init(key, shape, dtype):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return normal_init(key, shape, dtype, stddev=1.0 / math.sqrt(fan_in))


def zeros_init(_key, shape, dtype):
    return jnp.zeros(shape, dtype)


def ones_init(_key, shape, dtype):
    return jnp.ones(shape, dtype)


def matmul(name, x, w):
    """The default projection ``x @ w`` (x: (B,S,in), w: (in,out));
    ``name`` is the weight's key, which a LoRA projection
    (``repro.models.lora.projection``) uses to find its adapter."""
    return jnp.einsum("bsd,dk->bsk", x, w)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def expand_rank(v, ndim: int):
    """Left-pad ``v`` with unit axes so it broadcasts against a rank-``ndim``
    array along trailing axes.  Explicit so the suite can run with
    ``jax_numpy_rank_promotion='raise'``."""
    return jnp.reshape(v, (1,) * (ndim - v.ndim) + v.shape)


def rms_norm(x, scale, eps: float = 1e-6, *, gemma_style: bool = False):
    """RMSNorm.  gemma_style uses (1 + scale) weighting."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    w = (1.0 + scale.astype(jnp.float32)) if gemma_style \
        else scale.astype(jnp.float32)
    return (x * expand_rank(w, x.ndim)).astype(dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    return (x * expand_rank(scale.astype(jnp.float32), x.ndim)
            + expand_rank(bias.astype(jnp.float32), x.ndim)).astype(dtype)


def apply_norm(cfg, x, params):
    if cfg.norm == "layernorm":
        return layer_norm(x, params["scale"], params["bias"], cfg.norm_eps)
    return rms_norm(x, params["scale"], cfg.norm_eps,
                    gemma_style=cfg.embed_scale)


def init_norm(cfg, key, d, dtype):
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    init = zeros_init if cfg.embed_scale else ones_init
    return {"scale": init(key, (d,), dtype)}


# ---------------------------------------------------------------------------
# Activations / softcap
# ---------------------------------------------------------------------------

def softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def gated_act(kind: str, gate, up):
    if kind == "geglu":
        return jax.nn.gelu(gate, approximate=True) * up
    if kind == "silu":
        return jax.nn.silu(gate) * up
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # (head_dim/2,)


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, head_dim); positions: (..., S)."""
    if theta <= 0:
        return x
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)
    pos = positions[..., None].astype(jnp.float32)             # (..., S, 1)
    angles = pos * expand_rank(freqs, pos.ndim)                # (..., S, hd/2)
    angles = angles[..., None, :]                              # (..., S, 1, hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, d_model: int):
    """Whisper-style fixed sinusoidal position embeddings."""
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-math.log(10_000.0) * dim / d_model)
    ang = pos * inv
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    return pe  # (S, d_model)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def padded_vocab(vocab_size: int, multiple: int = 256) -> int:
    """Storage rows of the embedding table.

    Odd vocabularies (whisper 51866, hymba 32001, mamba2 50280) cannot be
    sharded on the 16-way model axis; padding the PARAMETER to a multiple
    of 256 (a standard implementation detail — ids never reach the pad
    rows, pad logits are masked to -inf) restores vocab-parallel
    unembedding.  §Perf iteration; semantics (cfg.vocab_size) unchanged.
    """
    if vocab_size % multiple == 0 or vocab_size < multiple:
        return vocab_size
    return vocab_size + (-vocab_size) % multiple


def embed_tokens(cfg, params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def unembed(cfg, params, x):
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum("...d,vd->...v", x, table)
    logits = softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    Vp = table.shape[0]
    if Vp != cfg.vocab_size:   # mask padded rows out of the softmax
        valid = jnp.arange(Vp) < cfg.vocab_size
        logits = jnp.where(expand_rank(valid, logits.ndim), logits, -1e30)
    return logits


def cross_entropy_loss(logits, labels, mask=None):
    """Mean token-level cross entropy.  logits f32 (..., V), labels int."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        return -jnp.mean(ll)
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
