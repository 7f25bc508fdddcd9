"""Model API: family dispatch for init / train_loss / serve_step.

All architectures expose:
    init_params(cfg, key, dtype)                -> params pytree
    train_loss(cfg, params, batch, remat=True, adapters=None)
                                                -> scalar loss (f32)
    init_cache(cfg, batch, cache_len, dtype)    -> decode cache pytree
    serve_step(cfg, params, cache, tokens, pos, seq_len) -> (logits, cache)
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.models import encdec, transformer

_DECODER_FAMILIES = ("dense", "moe", "ssm", "hybrid", "vlm")


def init_params(cfg, key, dtype=jnp.bfloat16):
    if cfg.family in _DECODER_FAMILIES:
        return transformer.init_decoder(cfg, key, dtype)
    if cfg.family == "encdec":
        return encdec.init_encdec(cfg, key, dtype)
    raise ValueError(f"unknown family {cfg.family!r}")


def train_loss(cfg, params, batch, *, remat: bool = True,
               unroll: bool = False, adapters=None):
    """``adapters``: LoRA adapters over the frozen ``params``
    (``repro.models.lora``; a ``layer_types`` model)."""
    if cfg.family in _DECODER_FAMILIES:
        return transformer.train_loss(cfg, params, batch, remat=remat,
                                      unroll=unroll, adapters=adapters)
    if adapters is not None:
        raise ValueError(f"no LoRA adapters for family {cfg.family!r}")
    if cfg.family == "encdec":
        return encdec.train_loss(cfg, params, batch, remat=remat,
                                 unroll=unroll)
    raise ValueError(f"unknown family {cfg.family!r}")


def forward_prefill(cfg, params, batch, *, remat: bool = True,
                    unroll: bool = False):
    """Prefill pass: returns last-position logits (B, V)."""
    if cfg.family in _DECODER_FAMILIES:
        hidden, _ = transformer.forward(cfg, params, batch["tokens"],
                                        remat=remat, unroll=unroll)
        from repro.models.common import unembed
        return unembed(cfg, params, hidden[:, -1])
    if cfg.family == "encdec":
        enc_out = encdec.encode(cfg, params, batch["encoder_embeds"],
                                remat=remat, unroll=unroll)
        hidden = encdec.decode_full(cfg, params, batch["tokens"], enc_out,
                                    remat=remat, unroll=unroll)
        from repro.models.common import unembed
        return unembed(cfg, params, hidden[:, -1])
    raise ValueError(f"unknown family {cfg.family!r}")


def init_cache(cfg, batch, cache_len, dtype=jnp.bfloat16):
    if cfg.family in _DECODER_FAMILIES:
        return transformer.init_cache(cfg, batch, cache_len, dtype)
    if cfg.family == "encdec":
        return encdec.init_cache(cfg, batch, cache_len, dtype)
    raise ValueError(f"unknown family {cfg.family!r}")


def serve_step(cfg, params, cache, tokens, pos, *, seq_len: int,
               unroll: bool = False):
    if cfg.family in _DECODER_FAMILIES:
        return transformer.serve_step(cfg, params, cache, tokens, pos,
                                      seq_len=seq_len, unroll=unroll)
    if cfg.family == "encdec":
        return encdec.serve_step(cfg, params, cache, tokens, pos,
                                 seq_len=seq_len, unroll=unroll)
    raise ValueError(f"unknown family {cfg.family!r}")
