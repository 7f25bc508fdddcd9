"""Unified decoder backbone for dense / moe / ssm / hybrid / vlm families.

Per-layer params are stacked on a leading axis and the stack runs under one
``jax.lax.scan`` (homogeneous layers; per-layer heterogeneity such as
gemma2's local/global alternation is expressed as a scanned per-layer
``window`` scalar).  ``jax.checkpoint`` wraps the body when remat is on.

A config with a per-layer mixer pattern (``cfg.layer_types``, granite-4.0-h)
runs its layers in the published order instead: each maximal run of
same-kind layers (``layer_runs``) is scanned over its own stacked
params, and no layer holds the other kind's.  Such a model may take LoRA
adapters (``repro.models.lora``) over a frozen base, and runs under the
device scopes ``model.mamba``, ``model.attention``, ``model.mlp``,
``model.lora`` and ``model.loss``.

Loss materialization: logits for 256k vocabularies are never materialized
for the full sequence — cross entropy runs in sequence chunks under
``jax.checkpoint`` (recompute in backward), bounding live memory.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import lora as lora_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (apply_norm, cross_entropy_loss, embed_tokens,
                                 expand_rank, init_norm, normal_init,
                                 padded_vocab, softcap, unembed)
from repro.sharding.context import constrain, shard_layer_param_cotangents


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_decoder(cfg, key, dtype):
    if cfg.layer_types:
        return init_pattern(cfg, key, dtype)
    ks = jax.random.split(key, 8)
    Vp = padded_vocab(cfg.vocab_size)
    params = {"embed": normal_init(ks[0], (Vp, cfg.d_model), dtype)}
    if not cfg.tie_embeddings:
        params["unembed"] = normal_init(ks[1], (Vp, cfg.d_model), dtype)

    blocks = {}
    L, d = cfg.n_layers, cfg.d_model
    blocks["ln1"] = _stack_norm(cfg, ks[2], L, d, dtype)
    if cfg.family != "ssm":
        blocks["attn"] = attn.init_attention(cfg, ks[3], dtype)
        blocks["ln2"] = _stack_norm(cfg, ks[4], L, d, dtype)
        if cfg.post_attn_norm:
            blocks["post_attn"] = _stack_norm(cfg, ks[4], L, d, dtype)
            blocks["post_mlp"] = _stack_norm(cfg, ks[5], L, d, dtype)
        if cfg.n_experts:
            blocks["moe"] = moe_mod.init_moe(cfg, ks[5], dtype)
        else:
            blocks["mlp"] = mlp_mod.init_mlp(cfg, ks[5], dtype)
    if cfg.family in ("ssm", "hybrid"):
        blocks["ssm"] = ssm_mod.init_ssm(cfg, ks[6], dtype)
    params["blocks"] = blocks
    params["final_norm"] = init_norm(cfg, ks[7], d, dtype)
    return params


def _stack_norm(cfg, key, L, d, dtype):
    one = init_norm(cfg, key, d, dtype)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (L,) + a.shape), one)


def layer_windows(cfg, seq_len: int) -> jnp.ndarray:
    """(L,) int32 effective attention window per layer (seq_len == full)."""
    out = []
    for i in range(cfg.n_layers):
        if cfg.sliding_window is not None and cfg.layer_is_local(i):
            out.append(min(cfg.sliding_window, seq_len))
        else:
            out.append(seq_len)
    return jnp.asarray(out, jnp.int32)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def _layer_body(cfg, x, lp, window, positions, *, unroll=False,
                chunked_local_window: Optional[int] = None):
    """One decoder layer.  x: (B,S,d).

    chunked_local_window: when set (static int), the layer uses the
    block-local attention path (``attn.attend_chunked``: computes only
    the window-adjacent chunks, S·W instead of S²/2 score FLOPs; enabled
    by ``REPRO_CHUNKED_LOCAL=1`` in ``forward``).
    """
    aux = jnp.float32(0.0)
    h = apply_norm(cfg, x, _idx(lp, "ln1"))
    if cfg.family == "ssm":
        x = x + ssm_mod.apply_ssm(cfg, lp["ssm"], h, unroll=unroll)
        return x, aux
    if chunked_local_window is not None:
        attn_out = attn.attend_chunked(cfg, lp["attn"], h, positions,
                                       chunked_local_window)
    else:
        attn_out = attn.attend_full(cfg, lp["attn"], h, positions, window,
                                    unroll=unroll)
    if cfg.family == "hybrid":
        ssm_out = ssm_mod.apply_ssm(cfg, lp["ssm"], h, unroll=unroll)
        attn_out = 0.5 * (attn_out + ssm_out)
    if cfg.post_attn_norm:
        attn_out = apply_norm(cfg, attn_out, _idx(lp, "post_attn"))
    x = x + attn_out
    h2 = apply_norm(cfg, x, _idx(lp, "ln2"))
    if cfg.n_experts:
        ff, aux = moe_mod.apply_moe(cfg, lp["moe"], h2)
    else:
        ff = mlp_mod.apply_mlp(cfg, lp["mlp"], h2)
    if cfg.post_attn_norm:
        ff = apply_norm(cfg, ff, _idx(lp, "post_mlp"))
    x = x + ff
    return x, aux


def _idx(lp, name):
    return lp[name]


def forward(cfg, params, tokens, *, remat: bool = True,
            positions: Optional[jnp.ndarray] = None, unroll: bool = False):
    """tokens (B,S) -> final hidden states (B,S,d) and aux loss.

    unroll=True runs a Python loop over layers (and inner chunk loops) so
    the compiled HLO has exact trip counts for cost analysis.
    """
    if cfg.layer_types:
        return forward_pattern(cfg, params, tokens, remat=remat,
                               positions=positions, unroll=unroll)
    B, S = tokens.shape
    x = constrain(embed_tokens(cfg, params, tokens))
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    windows = layer_windows(cfg, S)

    chunked_local = (
        os.environ.get("REPRO_CHUNKED_LOCAL") == "1"
        and cfg.sliding_window is not None
        and cfg.local_global_period == 2
        and cfg.n_layers % 2 == 0
        and S > 2 * cfg.sliding_window)

    if chunked_local:
        # §Perf: scan over (local, global) layer PAIRS so the local layer
        # can take the block-local attention path with a STATIC window.
        W = int(cfg.sliding_window)
        pair_blocks = jax.tree_util.tree_map(
            lambda a: a.reshape((cfg.n_layers // 2, 2) + a.shape[1:]),
            params["blocks"])

        def pair_body(carry, lp2):
            x, aux = carry
            lp_loc = jax.tree_util.tree_map(lambda a: a[0], lp2)
            lp_glb = jax.tree_util.tree_map(lambda a: a[1], lp2)
            lp_loc = shard_layer_param_cotangents(lp_loc)
            lp_glb = shard_layer_param_cotangents(lp_glb)
            x, a1 = _layer_body(cfg, x, lp_loc, None, positions,
                                unroll=unroll, chunked_local_window=W)
            x = constrain(x)
            x, a2 = _layer_body(cfg, x, lp_glb, jnp.int32(S), positions,
                                unroll=unroll)
            return (constrain(x), aux + a1 + a2), None

        if remat:
            pair_body = jax.checkpoint(pair_body)
        if unroll:
            carry = (x, jnp.float32(0.0))
            for li in range(cfg.n_layers // 2):
                lp2 = jax.tree_util.tree_map(lambda a: a[li], pair_blocks)
                carry, _ = pair_body(carry, lp2)
            x, aux = carry
        else:
            (x, aux), _ = jax.lax.scan(pair_body, (x, jnp.float32(0.0)),
                                       pair_blocks)
        x = apply_norm(cfg, x, params["final_norm"])
        return x, aux

    def body(carry, per_layer):
        x, aux = carry
        lp, window = per_layer
        lp = shard_layer_param_cotangents(lp)
        x, a = _layer_body(cfg, x, lp, window, positions, unroll=unroll)
        return (constrain(x), aux + a), None

    if remat:
        body = jax.checkpoint(body)
    if unroll:
        carry = (x, jnp.float32(0.0))
        for li in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[li], params["blocks"])
            carry, _ = body(carry, (lp, windows[li]))
        x, aux = carry
    else:
        (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                   (params["blocks"], windows))
    x = apply_norm(cfg, x, params["final_norm"])
    return x, aux


def chunked_loss(cfg, params, hidden, labels, mask=None, chunk: int = 512,
                 unroll: bool = False, logits_fn=None):
    """Cross entropy over sequence chunks (never materializes (B,S,V)).
    ``logits_fn(cfg, params, h)``: the unembedding (``unembed``)."""
    logits_fn = logits_fn or unembed
    B, S, _ = hidden.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        base = jnp.ones_like(labels).at[:, S:].set(0)
        mask = base if mask is None else jnp.pad(mask, ((0, 0), (0, pad)))
        S = S + pad
    nc = S // chunk

    @jax.checkpoint
    def one(h_c, y_c, m_c):
        logits = logits_fn(cfg, params, h_c)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, y_c[..., None], axis=-1)[..., 0]
        m = m_c.astype(jnp.float32)
        return jnp.sum(-ll * m), jnp.sum(m)

    hs = hidden.reshape(B, nc, chunk, -1).transpose(1, 0, 2, 3)
    ys = labels.reshape(B, nc, chunk).transpose(1, 0, 2)
    ms = (jnp.ones_like(labels) if mask is None else mask) \
        .reshape(B, nc, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        tot, cnt = carry
        l, c = one(*xs)
        return (tot + l, cnt + c), None

    if unroll:
        carry = (jnp.float32(0.0), jnp.float32(0.0))
        for ci in range(nc):
            carry, _ = body(carry, (hs[ci], ys[ci], ms[ci]))
        tot, cnt = carry
    else:
        (tot, cnt), _ = jax.lax.scan(
            body, (jnp.float32(0.0), jnp.float32(0.0)), (hs, ys, ms))
    return tot / jnp.maximum(cnt, 1.0)


def train_loss(cfg, params, batch, *, remat: bool = True,
               unroll: bool = False, adapters=None):
    """Next-token LM loss.  batch: {"tokens": (B,S)} (+ optional mask).
    ``adapters``: a ``lora.LoRA`` over the frozen ``params`` (a
    ``layer_types`` model only)."""
    if cfg.layer_types:
        return pattern_loss(cfg, params, batch, adapters, remat=remat,
                            unroll=unroll)
    if adapters is not None:
        raise ValueError("LoRA adapters need a layer_types model")
    tokens = batch["tokens"]
    hidden, aux = forward(cfg, params, tokens[:, :-1], remat=remat,
                          unroll=unroll)
    labels = tokens[:, 1:]
    mask = batch.get("mask")
    if mask is not None:
        mask = mask[:, 1:]
    loss = chunked_loss(cfg, params, hidden, labels, mask, unroll=unroll)
    if cfg.n_experts:
        loss = loss + cfg.router_aux_coef * aux / cfg.n_layers
    return loss


# ---------------------------------------------------------------------------
# Decode (single token) with stacked caches scanned over layers
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, cache_len: int, dtype):
    if cfg.layer_types:
        raise NotImplementedError("decode of a layer_types model")
    cache = {}
    if cfg.family != "ssm":
        cache["kv"] = attn.init_kv_cache(cfg, batch, cache_len, dtype)
    if cfg.family in ("ssm", "hybrid"):
        cache["ssm"] = ssm_mod.init_ssm_state(cfg, batch)
    return cache


def serve_step(cfg, params, cache, tokens, pos, *, seq_len: int,
               unroll: bool = False):
    """Decode one token.  tokens (B,1); pos scalar int32.

    ``seq_len`` is the logical max sequence; ring buffering activates when
    the allocated cache is shorter (windowed long-context decode).
    """
    B = tokens.shape[0]
    x = embed_tokens(cfg, params, tokens)
    windows = layer_windows(cfg, seq_len)

    cache_len = None
    ring = False
    quantized = False
    if "kv" in cache:
        cache_len = cache["kv"]["k"].shape[2]
        ring = cache_len < seq_len
        quantized = "k_scale" in cache["kv"]

    def body(x, per_layer):
        lp, window, layer_cache = (per_layer["params"], per_layer["window"],
                                   per_layer["cache"])
        h = apply_norm(cfg, x, _idx(lp, "ln1"))
        new_cache = {}
        if cfg.family == "ssm":
            out, new_h, new_conv = ssm_mod.decode_ssm(
                cfg, lp["ssm"], h, layer_cache["ssm_h"],
                layer_cache["ssm_conv"])
            new_cache.update(ssm_h=new_h, ssm_conv=new_conv)
            return x + out, new_cache
        eff_window = jnp.minimum(window, seq_len)
        if quantized:
            a_out, qc = attn.decode_attend_quantized(
                cfg, lp["attn"], h,
                {k: layer_cache[k] for k in
                 ("k", "v", "k_scale", "v_scale")},
                pos, eff_window, ring=ring)
            new_cache.update(qc)
        else:
            a_out, nk, nv = attn.decode_attend(
                cfg, lp["attn"], h, layer_cache["k"], layer_cache["v"],
                pos, eff_window, ring=ring)
            new_cache.update(k=nk, v=nv)
        if cfg.family == "hybrid":
            s_out, new_h, new_conv = ssm_mod.decode_ssm(
                cfg, lp["ssm"], h, layer_cache["ssm_h"],
                layer_cache["ssm_conv"])
            new_cache.update(ssm_h=new_h, ssm_conv=new_conv)
            a_out = 0.5 * (a_out + s_out)
        if cfg.post_attn_norm:
            a_out = apply_norm(cfg, a_out, _idx(lp, "post_attn"))
        x = x + a_out
        h2 = apply_norm(cfg, x, _idx(lp, "ln2"))
        if cfg.n_experts:
            ff, _ = moe_mod.apply_moe(cfg, lp["moe"], h2,
                                      capacity_factor=2.0)
        else:
            ff = mlp_mod.apply_mlp(cfg, lp["mlp"], h2)
        if cfg.post_attn_norm:
            ff = apply_norm(cfg, ff, _idx(lp, "post_mlp"))
        return x + ff, new_cache

    layer_cache = {}
    if "kv" in cache:
        layer_cache["k"] = cache["kv"]["k"]
        layer_cache["v"] = cache["kv"]["v"]
        if quantized:
            layer_cache["k_scale"] = cache["kv"]["k_scale"]
            layer_cache["v_scale"] = cache["kv"]["v_scale"]
    if "ssm" in cache:
        layer_cache["ssm_h"] = cache["ssm"]["h"]
        layer_cache["ssm_conv"] = cache["ssm"]["conv"]

    xs = {"params": params["blocks"], "window": windows,
          "cache": layer_cache}
    if unroll:
        updates = []
        for li in range(cfg.n_layers):
            per = jax.tree_util.tree_map(lambda a: a[li], xs)
            x, upd = body(x, per)
            updates.append(upd)
        new_layer_cache = jax.tree_util.tree_map(
            lambda *us: jnp.stack(us), *updates)
    else:
        x, new_layer_cache = jax.lax.scan(body, x, xs)

    new_cache = {}
    if "kv" in cache:
        new_cache["kv"] = {"k": new_layer_cache["k"],
                           "v": new_layer_cache["v"]}
        if quantized:
            new_cache["kv"]["k_scale"] = new_layer_cache["k_scale"]
            new_cache["kv"]["v_scale"] = new_layer_cache["v_scale"]
    if "ssm" in cache:
        new_cache["ssm"] = {"h": new_layer_cache["ssm_h"],
                            "conv": new_layer_cache["ssm_conv"]}

    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params, x)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Per-layer mixer pattern (cfg.layer_types: granite-4.0-h)
# ---------------------------------------------------------------------------

def layer_runs(cfg):
    """[(kind, n)]: the maximal runs of same-kind layers of
    ``cfg.layer_types``, in order (granite's first period: mamba x5,
    attention x1, mamba x4)."""
    runs = []
    for kind in cfg.layer_types:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return [(k, n) for k, n in runs]


def init_pattern(cfg, key, dtype):
    """Params of a ``layer_types`` model: ``embed``, ``runs`` (one dict
    per run, each leaf stacked over the run's layers: ``ln1``, the mixer
    ``ssm`` or ``attn``, ``ln2``, ``mlp``) and ``final_norm``."""
    if len(cfg.layer_types) != cfg.n_layers:
        raise ValueError(f"{len(cfg.layer_types)} layer_types for "
                         f"{cfg.n_layers} layers")
    runs_kn = layer_runs(cfg)
    ks = jax.random.split(key, 3 + len(runs_kn))
    d = cfg.d_model
    Vp = padded_vocab(cfg.vocab_size)
    params = {"embed": normal_init(ks[0], (Vp, d), dtype),
              "final_norm": init_norm(cfg, ks[1], d, dtype)}
    if not cfg.tie_embeddings:
        params["unembed"] = normal_init(ks[2], (Vp, d), dtype)
    runs = []
    for (kind, n), k in zip(runs_kn, ks[3:]):
        k = jax.random.split(k, 4)
        run = {"ln1": _stack_norm(cfg, k[0], n, d, dtype),
               "ln2": _stack_norm(cfg, k[1], n, d, dtype),
               "mlp": mlp_mod.init_mlp(cfg, k[2], dtype, n_layers=n)}
        if kind == "mamba":
            run["ssm"] = ssm_mod.init_ssm(cfg, k[3], dtype, n_layers=n)
        elif kind == "attention":
            run["attn"] = attn.init_attention(cfg, k[3], dtype, n_layers=n)
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        runs.append(run)
    params["runs"] = tuple(runs)
    return params


def _mamba_mixer(cfg, lp, h, ad, scale, positions, unroll):
    with jax.named_scope("model.mamba"):
        return ssm_mod.apply_ssm(cfg, lp["ssm"], h, unroll=unroll,
                                 dense=lora_mod.projection(ad.get("ssm"),
                                                           scale))


def _attention_mixer(cfg, lp, h, ad, scale, positions, unroll):
    with jax.named_scope("model.attention"):
        return attn.attend_full(cfg, lp["attn"], h, positions, None,
                                unroll=unroll,
                                dense=lora_mod.projection(ad.get("attn"),
                                                          scale))


def _pattern_layer(cfg, mixer, x, lp, ad, scale, positions, unroll):
    """One layer: x + r * mixer(norm(x)), then x + r * mlp(norm(x)), r
    the residual multiplier.  ``ad``: the layer's adapters or None."""
    ad = ad or {}
    r = cfg.residual_multiplier
    x = x + r * mixer(cfg, lp, apply_norm(cfg, x, lp["ln1"]), ad, scale,
                      positions, unroll)
    with jax.named_scope("model.mlp"):
        h = apply_norm(cfg, x, lp["ln2"])
        f = mlp_mod.apply_mlp(cfg, lp["mlp"], h,
                              dense=lora_mod.projection(ad.get("mlp"),
                                                        scale))
    return x + r * f


def forward_pattern(cfg, params, tokens, *, adapters=None,
                    remat: bool = True, positions=None,
                    unroll: bool = False):
    """tokens (B,S) -> final hidden states (B,S,d) f32 and a zero aux
    loss.  The residual stream is float32; each projection computes in
    its weight's dtype with float32 accumulation (``lora.linear``)."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
    x = (jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
         * cfg.embedding_multiplier)
    scale = adapters.scale if adapters is not None else 1.0
    ad_runs = (adapters.layers if adapters is not None
               else (None,) * len(params["runs"]))
    mixers = {"mamba": _mamba_mixer, "attention": _attention_mixer}
    for (kind, n), lp_run, ad_run in zip(layer_runs(cfg), params["runs"],
                                         ad_runs):
        def body(x, per, mixer=mixers[kind]):
            lp, ad = per
            return _pattern_layer(cfg, mixer, x, lp, ad, scale, positions,
                                  unroll), None

        if remat:
            body = jax.checkpoint(body)
        if unroll:
            for li in range(n):
                x, _ = body(x, jax.tree_util.tree_map(
                    lambda a: a[li], (lp_run, ad_run)))
        else:
            x, _ = jax.lax.scan(body, x, (lp_run, ad_run))
    x = apply_norm(cfg, x, params["final_norm"])
    return x, jnp.float32(0.0)


def pattern_logits(cfg, params, h):
    """Logits over ``logits_scaling``: h (..., d) against the (tied)
    table, in the table's dtype with float32 accumulation."""
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum("...d,vd->...v", h.astype(table.dtype), table,
                        preferred_element_type=jnp.float32)
    logits = logits / cfg.logits_scaling
    if table.shape[0] != cfg.vocab_size:
        valid = jnp.arange(table.shape[0]) < cfg.vocab_size
        logits = jnp.where(expand_rank(valid, logits.ndim), logits, -1e30)
    return logits


def pattern_loss_chunk(cfg) -> int:
    """Sequence chunk of the loss: about 2^23 logits a sequence at once
    (64 positions at a 100k vocabulary), at most 512."""
    v = padded_vocab(cfg.vocab_size)
    return int(max(16, min(512, 2 ** int(math.log2(max(1, 2 ** 23 // v))))))


def pattern_loss(cfg, params, batch, adapters=None, *, remat: bool = True,
                 unroll: bool = False):
    """Next-token loss of a ``layer_types`` model (base ``params``,
    optional ``adapters``)."""
    tokens = batch["tokens"]
    hidden, _ = forward_pattern(cfg, params, tokens[:, :-1],
                                adapters=adapters, remat=remat,
                                unroll=unroll)
    mask = batch.get("mask")
    with jax.named_scope("model.loss"):
        return chunked_loss(cfg, params, hidden, tokens[:, 1:],
                            None if mask is None else mask[:, 1:],
                            chunk=pattern_loss_chunk(cfg), unroll=unroll,
                            logits_fn=pattern_logits)
