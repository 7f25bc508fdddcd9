"""Grouped-query attention with sliding-window, softcap, and KV-cache decode.

Two full-sequence paths:
  * ``attend_full``    — masked dense attention (baseline; window via mask)
  * ``attend_chunked`` — block-local attention that only computes the
    window-adjacent chunks (beyond-paper optimization; used when
    ``chunked_local=True`` and a window is set).  Saves O(S/W) of the
    attention FLOPs for local layers at long sequence lengths.

Decode path attends a single query token against a (ring-buffered) cache.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

import os

from repro.models.common import (apply_rope, expand_rank, fan_in_init,
                                 matmul, softcap, zeros_init)

NEG_INF = -2.0 ** 30


def init_attention(cfg, key, dtype, *, cross: bool = False, n_layers=None):
    L = n_layers if n_layers is not None else cfg.n_layers
    d, q_dim = cfg.d_model, cfg.n_heads * cfg.head_dim
    kv_dim = cfg.n_kv_heads * cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": fan_in_init(ks[0], (L, d, q_dim), dtype),
        "wk": fan_in_init(ks[1], (L, d, kv_dim), dtype),
        "wv": fan_in_init(ks[2], (L, d, kv_dim), dtype),
        "wo": fan_in_init(ks[3], (L, q_dim, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((L, q_dim), dtype)
        p["bk"] = jnp.zeros((L, kv_dim), dtype)
        p["bv"] = jnp.zeros((L, kv_dim), dtype)
    return p


def _project_qkv(cfg, lp, x, positions, *, rope: bool = True,
                 dense=matmul):
    """x: (B,S,d) -> q (B,S,H,hd), k/v (B,S,KV,hd).  ``dense(name, x,
    w)`` computes each projection.  No rotary embedding under
    ``position_embedding == "nope"``."""
    B, S, _ = x.shape
    q = dense("wq", x, lp["wq"])
    k = dense("wk", x, lp["wk"])
    v = dense("wv", x, lp["wv"])
    if "bq" in lp:
        q = q + expand_rank(lp["bq"], q.ndim)
        k = k + expand_rank(lp["bk"], k.ndim)
        v = v + expand_rank(lp["bv"], v.ndim)
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if rope and cfg.rope_theta > 0 and cfg.position_embedding != "nope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # (§Perf note: an attempted "project-then-gather-KV" constraint here
    # REGRESSED collective time 3.0->3.6s on gemma2 train_4k — GSPMD's own
    # propagation was already better; see EXPERIMENTS.md §Perf.)
    return q, k, v


def _scores_to_out(cfg, q, k, v, mask):
    """q (B,Sq,H,hd), k/v (B,Sk,KV,hd), mask broadcastable (B,1,Sq,Sk)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    group = H // KV
    qg = q.reshape(B, Sq, KV, group, hd)
    scale = (cfg.attention_multiplier if cfg.attention_multiplier is not None
             else 1.0 / math.sqrt(hd))
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = softcap(scores, cfg.attn_softcap)
    scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask,
                       scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def causal_mask(Sq: int, Sk: int, window) -> jnp.ndarray:
    """(1,1,Sq,Sk) boolean; window may be a traced scalar (None => full)."""
    qi = jnp.arange(Sq)[:, None] + (Sk - Sq)
    kj = jnp.arange(Sk)[None, :]
    m = kj <= qi
    if window is not None:
        m = m & (qi - kj < window)
    return m[None, None]


def attend_full(cfg, lp, x, positions, window=None, *, rope=True,
                q_chunk: int = None, unroll: bool = False, dense=matmul):
    if q_chunk is None:
        q_chunk = int(os.environ.get("REPRO_Q_CHUNK", "1024"))
    """Masked attention over the full sequence.

    For S > q_chunk the query dimension is processed in chunks (bounding
    the S x S score buffer to q_chunk x S — the XLA stand-in for the
    Pallas flash kernel).  ``unroll=True`` replaces the chunk scan with a
    Python loop so cost_analysis counts every trip (dry-run analysis
    variants only).
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(cfg, lp, x, positions, rope=rope, dense=dense)
    if S <= q_chunk:
        mask = causal_mask(S, S, window)
        out = _scores_to_out(cfg, q, k, v, mask)
        return dense("wo", out.reshape(B, S, -1), lp["wo"])

    QC = q_chunk
    pad = (-S) % QC
    if pad:  # keep chunks homogeneous
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = (S + pad) // QC
    kj = jnp.arange(S)[None, :]

    def one_chunk(ci, q_c):
        qi = ci * QC + jnp.arange(QC)[:, None]
        m = kj <= qi
        if window is not None:
            m = m & (qi - kj < window)
        return _scores_to_out(cfg, q_c, k, v, m[None, None])

    if unroll:
        outs = [one_chunk(jnp.int32(ci), q[:, ci * QC:(ci + 1) * QC])
                for ci in range(nq)]
        out = jnp.concatenate(outs, axis=1)
    else:
        qr = q.reshape(B, nq, QC, cfg.n_heads, cfg.head_dim) \
            .transpose(1, 0, 2, 3, 4)

        def body(_, inp):
            ci, q_c = inp
            return None, one_chunk(ci, q_c)

        _, outs = jax.lax.scan(body, None,
                               (jnp.arange(nq, dtype=jnp.int32), qr))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(B, S + pad, -1)
    out = out[:, :S] if pad else out.reshape(B, S, -1)
    return dense("wo", out.reshape(B, S, -1), lp["wo"])


def attend_chunked(cfg, lp, x, positions, window: int, *, rope=True):
    """Block-local attention: queries in chunk c attend to chunks c-1, c.

    Requires S % window == 0.  Exact for any sliding window <= chunk size
    (we set chunk = window).  FLOPs: 2*S*W*d instead of S^2*d/2.
    """
    B, S, _ = x.shape
    W = window
    if S % W != 0:
        return attend_full(cfg, lp, x, positions, window, rope=rope)
    q, k, v = _project_qkv(cfg, lp, x, positions, rope=rope)
    C = S // W
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    qc = q.reshape(B, C, W, H, hd)
    kc = k.reshape(B, C, W, KV, hd)
    vc = v.reshape(B, C, W, KV, hd)
    # previous chunk (zero for c=0, masked out anyway)
    kp = jnp.pad(kc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    vp = jnp.pad(vc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    k2 = jnp.concatenate([kp, kc], axis=2)  # (B,C,2W,KV,hd)
    v2 = jnp.concatenate([vp, vc], axis=2)

    group = H // KV
    qg = qc.reshape(B, C, W, KV, group, hd)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bcqkgh,bcskh->bckgqs", qg.astype(jnp.float32),
                        k2.astype(jnp.float32)) * scale
    scores = softcap(scores, cfg.attn_softcap)

    qi = jnp.arange(W)[:, None] + W           # position within the 2W window
    kj = jnp.arange(2 * W)[None, :]
    mask = (kj <= qi) & (qi - kj < W)         # causal + window
    first = jnp.arange(C)[:, None, None] == 0
    valid = jnp.where(first, kj[None] >= W, True)  # chunk 0 has no prev
    mask = mask[None] & valid                  # (C,W,2W)
    scores = jnp.where(mask[None, :, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bckgqs,bcskh->bcqkgh", probs, v2.astype(jnp.float32))
    out = out.reshape(B, S, H * hd).astype(x.dtype)
    return jnp.einsum("bsq,qd->bsd", out, lp["wo"])


# ---------------------------------------------------------------------------
# Decode (single new token against a cache)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg, batch: int, cache_len: int, dtype):
    """dtype jnp.int8 selects the quantized cache layout (per-(token,head)
    absmax scales) — halves decode HBM vs bf16; see EXPERIMENTS.md §Dry-run
    note (‡) on qwen1.5-32b decode_32k capacity."""
    shape = (cfg.n_layers, batch, cache_len, cfg.n_kv_heads, cfg.head_dim)
    if dtype == jnp.int8:
        sshape = shape[:-1]
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.bfloat16),
                "v_scale": jnp.zeros(sshape, jnp.bfloat16)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def quantize_kv(x):
    """x: (..., hd) -> (int8 values, bf16 scales)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def dequantize_kv(q, scale):
    return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]


def decode_attend(cfg, lp, x, cache_k, cache_v, pos, window=None, *,
                  rope=True, ring: bool = False):
    """One-token decode.  x: (B,1,d); cache_[kv]: (B,L_cache,KV,hd);
    pos: scalar int32 current position.  Returns (out (B,1,d), new_k, new_v).

    ring=True treats the cache as a ring buffer of size L_cache (used when
    the cache is smaller than the logical sequence, i.e. windowed decode).
    """
    B = x.shape[0]
    L_cache = cache_k.shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _project_qkv(cfg, lp, x, positions, rope=rope)
    slot = jnp.where(jnp.asarray(ring), pos % L_cache,
                     jnp.minimum(pos, L_cache - 1))
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k.astype(cache_k.dtype), (0, slot, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v.astype(cache_v.dtype), (0, slot, 0, 0))

    idx = jnp.arange(L_cache)
    if ring:
        # entry at idx holds logical position: reconstructed from ring layout
        logical = jnp.where(idx <= slot, pos - (slot - idx),
                            pos - (slot + L_cache - idx))
        valid = logical >= 0
    else:
        logical = idx
        valid = idx <= pos
    if window is not None:
        valid = valid & (pos - logical < window)
    mask = valid[None, None, None, :]  # (1,1,1,L_cache)

    out = _scores_to_out(cfg, q, cache_k, cache_v, mask)
    out = jnp.einsum("bsq,qd->bsd", out.reshape(B, 1, -1), lp["wo"])
    return out, cache_k, cache_v


def decode_attend_quantized(cfg, lp, x, qcache, pos, window=None, *,
                            rope=True, ring: bool = False):
    """int8-KV decode: dequantize-on-read, quantize-on-write.

    qcache: {k, v: int8 (B,L,KV,hd); k_scale, v_scale: bf16 (B,L,KV)}.
    Returns (out, new_cache_dict).
    """
    B = x.shape[0]
    L_cache = qcache["k"].shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _project_qkv(cfg, lp, x, positions, rope=rope)

    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    slot = jnp.where(jnp.asarray(ring), pos % L_cache,
                     jnp.minimum(pos, L_cache - 1))
    new = {}
    new["k"] = jax.lax.dynamic_update_slice(qcache["k"], kq, (0, slot, 0, 0))
    new["v"] = jax.lax.dynamic_update_slice(qcache["v"], vq, (0, slot, 0, 0))
    new["k_scale"] = jax.lax.dynamic_update_slice(
        qcache["k_scale"], ks, (0, slot, 0))
    new["v_scale"] = jax.lax.dynamic_update_slice(
        qcache["v_scale"], vs, (0, slot, 0))

    k_f = dequantize_kv(new["k"], new["k_scale"]).astype(q.dtype)
    v_f = dequantize_kv(new["v"], new["v_scale"]).astype(q.dtype)

    idx = jnp.arange(L_cache)
    if ring:
        logical = jnp.where(idx <= slot, pos - (slot - idx),
                            pos - (slot + L_cache - idx))
        valid = logical >= 0
    else:
        logical = idx
        valid = idx <= pos
    if window is not None:
        valid = valid & (pos - logical < window)
    mask = valid[None, None, None, :]
    out = _scores_to_out(cfg, q, k_f, v_f, mask)
    out = jnp.einsum("bsq,qd->bsd", out.reshape(B, 1, -1), lp["wo"])
    return out, new


def cross_attend(cfg, lp, x, enc_k, enc_v):
    """Cross attention (whisper decoder).  enc_[kv]: (B,S_enc,KV,hd)."""
    B, Sq, _ = x.shape
    q = jnp.einsum("bsd,dq->bsq", x, lp["wq"])
    if "bq" in lp:
        q = q + expand_rank(lp["bq"], q.ndim)
    q = q.reshape(B, Sq, cfg.n_heads, cfg.head_dim)
    mask = jnp.ones((1, 1, Sq, enc_k.shape[1]), bool)
    out = _scores_to_out(cfg, q, enc_k, enc_v, mask)
    return jnp.einsum("bsq,qd->bsd", out.reshape(B, Sq, -1), lp["wo"])


def project_cross_kv(cfg, lp, enc_out):
    """Precompute cross-attention K/V from encoder output (done once)."""
    B, S, _ = enc_out.shape
    k = jnp.einsum("bsd,dk->bsk", enc_out, lp["wk"])
    v = jnp.einsum("bsd,dk->bsk", enc_out, lp["wv"])
    if "bk" in lp:
        k = k + expand_rank(lp["bk"], k.ndim)
        v = v + expand_rank(lp["bv"], v.ndim)
    return (k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim),
            v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim))
