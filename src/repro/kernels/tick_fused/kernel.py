"""Fused device-tick Pallas kernels (TPU target).

Three kernels over the [C, D] client block, one per tick pass the
device engine historically ran as separate XLA ops.  The apply →
cascade → deliver data dependency (the cascade writes the post-apply
server vector into the broadcast ring that delivery then gathers from)
forces the three-way split; within each kernel the gather, the bucket
reduction, and the ring scatter fuse with their masks and stepsize
scaling so the [C, D] traffic is a single HBM pass.

Layout (``repro.kernels.tiling``): per-client scalars and masks are
``(C, 1)`` columns, server vectors ``(1, D)`` rows, and the client
kernels run on a ``(D tiles, C tiles)`` grid with C innermost.  The
scatter's ring sums accumulate across C tiles in a resident output
block, so they add within a tile first and then tile by tile.  Ring
axes (B broadcast slots, G scatter rows) are small and unroll as Python
loops: the broadcast gather is a select-accumulate (pure selection, no
float sums, bitwise equal to ``bc_v[best]``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# D tiles are independent; the C axis revisits the resident ring-sum
# output block, so it runs in order
_CLIENT_GRID = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _col(ref, shape):
    """A ``(rows, 1)`` column block broadcast across the lanes."""
    return jnp.broadcast_to(ref[...], shape)


def _bucket_apply_kernel(v_ref, rows_ref, dec_ref, flag_ref, out_ref, *,
                         single: bool):
    v = v_ref[...]
    rows = rows_ref[...]
    dec = dec_ref[...]
    if single:
        # size-1 bucket: scale-by-weight, not a sum — jnp.sum over a
        # size-1 axis computes 0.0 + x and flips a -0.0 row
        contrib = rows[0:1] * dec[0:1]
    else:
        contrib = jnp.sum(rows * dec, axis=0, keepdims=True)
    out_ref[...] = jnp.where(_col(flag_ref, v.shape) != 0, v - contrib, v)


def _tick_deliver_kernel(w_ref, u_ref, bc_ref, best_ref, take_ref,
                         eta_ref, out_ref, *, B: int):
    w = w_ref[...]
    best = _col(best_ref, w.shape)
    gathered = jnp.zeros_like(w)
    for b in range(B):
        gathered = jnp.where(best == b, bc_ref[b:b + 1, :], gathered)
    out_ref[...] = jnp.where(_col(take_ref, w.shape) != 0,
                             gathered - eta_ref[...] * u_ref[...], w)


def _tick_scatter_kernel(sent_ref, w_ref, u_ref, wgt_ref, done_ref,
                         eta_ref, w_out, u_out, vec_out, *, G: int,
                         dp_on: bool):
    c = pl.program_id(1)
    sent = sent_ref[...]
    wgt = wgt_ref[...]
    for g in range(G):
        part = jnp.sum(sent * wgt[:, g:g + 1], axis=0, keepdims=True)

        # the first C tile stores, later tiles add: no 0.0 + x term
        @pl.when(c == 0)
        def _store(g=g, part=part):
            vec_out[g:g + 1, :] = part

        @pl.when(c > 0)
        def _add(g=g, part=part):
            vec_out[g:g + 1, :] += part

    done = _col(done_ref, sent.shape) != 0
    if dp_on:
        w_out[...] = jnp.where(
            done, w_ref[...] + eta_ref[...] * (sent - u_ref[...]),
            w_ref[...])
    else:
        w_out[...] = w_ref[...]
    u_out[...] = jnp.where(done, jnp.zeros_like(sent), sent)


def bucket_apply_kernel(v, rows, dec, flag, *, d_block: int,
                        interpret: bool = True):
    """v: (1, D), rows: (A, D), dec: (A, 1), flag: (1, 1) int32;
    D % d_block == 0.  Returns the updated server row (1, D).
    """
    A, D = rows.shape
    assert D % d_block == 0, (D, d_block)
    return pl.pallas_call(
        functools.partial(_bucket_apply_kernel, single=(A == 1)),
        name="bucket_apply",
        grid=(D // d_block,),
        in_specs=[
            pl.BlockSpec((1, d_block), lambda d: (0, d)),
            pl.BlockSpec((A, d_block), lambda d: (0, d)),
            pl.BlockSpec((A, 1), lambda d: (0, 0)),
            pl.BlockSpec((1, 1), lambda d: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, d_block), lambda d: (0, d)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        interpret=interpret,
    )(v, rows, dec, flag)


def tick_deliver_kernel(w, U, bc_v, best, take, eta, *, c_block: int,
                        d_block: int, interpret: bool = True):
    """w, U: (C, D); bc_v: (B, D); best, take: (C, 1) int32;
    eta: (C, 1).  C % c_block == 0, D % d_block == 0.

    Returns the updated weights (C, D).
    """
    C, D = w.shape
    B = bc_v.shape[0]
    assert C % c_block == 0 and D % d_block == 0, (C, c_block, D, d_block)
    blk = pl.BlockSpec((c_block, d_block), lambda d, c: (c, d))
    col = pl.BlockSpec((c_block, 1), lambda d, c: (c, 0))
    return pl.pallas_call(
        functools.partial(_tick_deliver_kernel, B=B),
        name="tick_deliver",
        grid=(D // d_block, C // c_block),
        in_specs=[blk, blk,
                  pl.BlockSpec((B, d_block), lambda d, c: (0, d)),
                  col, col, col],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((C, D), jnp.float32),
        compiler_params=_CLIENT_GRID,
        interpret=interpret,
    )(w, U, bc_v, best, take, eta)


def tick_scatter_kernel(sent, w, U, wgt, done, eta, *, dp_on: bool,
                        c_block: int, d_block: int,
                        interpret: bool = True):
    """sent, w, U: (C, D); wgt: (C, G) per-client ring-row weights;
    done: (C, 1) int32; eta: (C, 1).  C % c_block == 0,
    D % d_block == 0.

    Returns (w_new (C, D), U_new (C, D), vec (G, D)), where
    ``vec[g] = sum_c wgt[c, g] * sent[c]`` — the caller adds it to ring
    row g under its any-arrival guard.
    """
    C, D = sent.shape
    G = wgt.shape[1]
    assert C % c_block == 0 and D % d_block == 0, (C, c_block, D, d_block)
    blk = pl.BlockSpec((c_block, d_block), lambda d, c: (c, d))
    col = pl.BlockSpec((c_block, 1), lambda d, c: (c, 0))
    return pl.pallas_call(
        functools.partial(_tick_scatter_kernel, G=G, dp_on=dp_on),
        name="tick_scatter",
        grid=(D // d_block, C // c_block),
        in_specs=[blk, blk, blk,
                  pl.BlockSpec((c_block, G), lambda d, c: (c, 0)),
                  col, col],
        out_specs=[blk, blk,
                   pl.BlockSpec((G, d_block), lambda d, c: (0, d))],
        out_shape=[
            jax.ShapeDtypeStruct((C, D), jnp.float32),
            jax.ShapeDtypeStruct((C, D), jnp.float32),
            jax.ShapeDtypeStruct((G, D), jnp.float32),
        ],
        compiler_params=_CLIENT_GRID,
        interpret=interpret,
    )(sent, w, U, wgt, done, eta)
