"""Fused per-client clip → noise → accumulate Pallas kernel (TPU target).

The cohort engine's round-completion hot spot (Algorithm 1 lines 17/23-24
lifted to a *batched* client population): given the round updates of a
cohort U (C, D) — C clients, D the flattened model dimension — produce

    out[c] = U[c] * min(1, clip / ||U[c]||_2) + noise_scale * N(0, 1)
    agg[d] = sum_c weight[c] * out[c, d]

for the rows selected by ``mask`` (non-finishing clients pass through
unchanged).  ``weight`` folds the server round step size eta(i_c) into the
reduction, so ``agg`` is exactly the vector the batched server subtracts
from the global model for one arrival bucket — the XLA baseline would
materialize the scaled+noised (C, D) copy and reduce it separately.

Layout (``repro.kernels.tiling``): per-client ``mask``/``weight``/squared
norms are ``(C, 1)`` columns and ``agg`` a ``(1, D)`` row.  A first pass
accumulates per-row squared norms over D tiles (grid ``(C tiles,
D tiles)``, D innermost); the second scales rows, adds noise and reduces
over C tiles (grid ``(D tiles, C tiles)``, C innermost), so ``agg`` adds
within a C tile first and then tile by tile.  Two noise paths:
  * operand noise (CPU/interpret-safe): standard normals are streamed in
    as a (C, D) input and the kernel fuses clip+add+reduce;
  * in-kernel PRNG (TPU only): ``pltpu.prng_random_bits`` + Box–Muller
    per tile, so the noise block never touches HBM.  The TPU PRNG
    primitives have no CPU lowering, hence no interpret mode for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the grid's inner axis accumulates into a resident output block
_ACCUMULATE = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _sqsum_kernel(u_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...].astype(jnp.float32)              # (c_block, d_block)
    out_ref[...] += jnp.sum(u * u, axis=1, keepdims=True)


def _scale_noise(u, sq, noise, mask, wgt, *, clip, noise_scale):
    """Shared tile math for both noise paths; sq/mask/wgt are columns."""
    if clip > 0.0:
        norms = jnp.sqrt(sq)
        scale = 1.0 / jnp.maximum(1.0, norms / clip)
    else:
        scale = jnp.ones_like(mask)
    scale = 1.0 + mask * (scale - 1.0)              # pass-through rows
    out = u * scale
    if noise_scale > 0.0:
        out = out + (noise_scale * mask) * noise
    return out, jnp.sum(out * wgt, axis=0, keepdims=True)


def _store_tiles(out, part, out_u_ref, out_agg_ref):
    out_u_ref[...] = out.astype(out_u_ref.dtype)

    # the first C tile stores, later tiles add: no 0.0 + x term
    @pl.when(pl.program_id(1) == 0)
    def _store():
        out_agg_ref[...] = part

    @pl.when(pl.program_id(1) > 0)
    def _add():
        out_agg_ref[...] += part


def _clip_noise_kernel(u_ref, sq_ref, noise_ref, mask_ref, wgt_ref,
                       out_u_ref, out_agg_ref, *, clip: float,
                       noise_scale: float):
    out, part = _scale_noise(
        u_ref[...].astype(jnp.float32), sq_ref[...], noise_ref[...],
        mask_ref[...], wgt_ref[...], clip=clip, noise_scale=noise_scale)
    _store_tiles(out, part, out_u_ref, out_agg_ref)


def _clip_noise_prng_kernel(seed_ref, u_ref, sq_ref, mask_ref, wgt_ref,
                            out_u_ref, out_agg_ref, *, clip: float,
                            noise_scale: float):
    # per-tile stream: the draw's seed and a tile id unique across the
    # (shard, D tile, C tile) grid (Mosaic takes at most two seeds)
    tile = ((seed_ref[1] * pl.num_programs(0) + pl.program_id(0))
            * pl.num_programs(1) + pl.program_id(1))
    pltpu.prng_seed(seed_ref[0], tile)
    shape = u_ref.shape
    # Box–Muller from two uniforms built off the top 24 bits.  The bits
    # come as int32: a logical shift keeps the uniforms non-negative.
    b1 = jax.lax.shift_right_logical(pltpu.prng_random_bits(shape), 8)
    b2 = jax.lax.shift_right_logical(pltpu.prng_random_bits(shape), 8)
    u1 = b1.astype(jnp.float32) * (1.0 / (1 << 24)) + 2.0 ** -25
    u2 = b2.astype(jnp.float32) * (1.0 / (1 << 24))
    normal = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * jnp.pi * u2)
    out, part = _scale_noise(
        u_ref[...].astype(jnp.float32), sq_ref[...], normal,
        mask_ref[...], wgt_ref[...], clip=clip, noise_scale=noise_scale)
    _store_tiles(out, part, out_u_ref, out_agg_ref)


def _row_sqsum(u, *, c_block: int, d_block: int, interpret: bool):
    C, D = u.shape
    return pl.pallas_call(
        _sqsum_kernel,
        name="row_sqsum",
        grid=(C // c_block, D // d_block),
        in_specs=[pl.BlockSpec((c_block, d_block), lambda c, d: (c, d))],
        out_specs=pl.BlockSpec((c_block, 1), lambda c, d: (c, 0)),
        out_shape=jax.ShapeDtypeStruct((C, 1), jnp.float32),
        compiler_params=_ACCUMULATE,
        interpret=interpret,
    )(u)


def _specs(c_block, d_block):
    blk = pl.BlockSpec((c_block, d_block), lambda d, c: (c, d))
    col = pl.BlockSpec((c_block, 1), lambda d, c: (c, 0))
    agg = pl.BlockSpec((1, d_block), lambda d, c: (0, d))
    return blk, col, agg


def cohort_clip_noise_kernel(u, noise, weights, mask, *, clip: float,
                             noise_scale: float, c_block: int,
                             d_block: int, interpret: bool = True):
    """Operand-noise path.  u, noise: (C, D); weights, mask: (C, 1);
    C % c_block == 0, D % d_block == 0.

    Returns (out (C, D), agg (1, D)).
    """
    C, D = u.shape
    assert C % c_block == 0 and D % d_block == 0, (C, c_block, D, d_block)
    sq = (_row_sqsum(u, c_block=c_block, d_block=d_block,
                     interpret=interpret)
          if clip > 0.0 else jnp.zeros((C, 1), jnp.float32))
    blk, col, agg = _specs(c_block, d_block)
    return pl.pallas_call(
        functools.partial(_clip_noise_kernel, clip=clip,
                          noise_scale=noise_scale),
        name="cohort_clip_noise",
        grid=(D // d_block, C // c_block),
        in_specs=[blk, col, blk, col, col],
        out_specs=[blk, agg],
        out_shape=[
            jax.ShapeDtypeStruct((C, D), jnp.float32),
            jax.ShapeDtypeStruct((1, D), jnp.float32),
        ],
        compiler_params=_ACCUMULATE,
        interpret=interpret,
    )(u, sq, noise, mask, weights)


def cohort_clip_noise_prng_kernel(u, seed, weights, mask, *, clip: float,
                                  noise_scale: float, c_block: int,
                                  d_block: int):
    """In-kernel-PRNG path (TPU only — no interpret/CPU lowering).

    seed: (2,) int32 — the draw's seed and the client shard index.
    Returns (out (C, D), agg (1, D)).
    """
    C, D = u.shape
    assert C % c_block == 0 and D % d_block == 0, (C, c_block, D, d_block)
    sq = (_row_sqsum(u, c_block=c_block, d_block=d_block, interpret=False)
          if clip > 0.0 else jnp.zeros((C, 1), jnp.float32))
    blk, col, agg = _specs(c_block, d_block)
    return pl.pallas_call(
        functools.partial(_clip_noise_prng_kernel, clip=clip,
                          noise_scale=noise_scale),
        name="cohort_clip_noise_prng",
        grid=(D // d_block, C // c_block),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk, col, col,
                  col],
        out_specs=[blk, agg],
        out_shape=[
            jax.ShapeDtypeStruct((C, D), jnp.float32),
            jax.ShapeDtypeStruct((1, D), jnp.float32),
        ],
        compiler_params=_ACCUMULATE,
        interpret=False,
    )(seed, u, sq, mask, weights)
