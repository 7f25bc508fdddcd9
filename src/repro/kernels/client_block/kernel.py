"""Fused local-SGD client block for the paper's logreg (TPU target).

One kernel runs a whole masked block of local SGD steps for a tile of
clients.  The grid is ``(C tiles, steps)``, steps innermost: the ``w``
and ``U`` output blocks do not move along the step axis, so they stay
resident in VMEM for all of a tile's steps — read once at step 0,
written back to HBM once after the last.  Each step's sample rows
(``X_aug = [X | y]`` rows, gathered before the call) stream in by
BlockSpec, one ``(c_block, D)`` block a step, and the logit dot, the
per-example clip norm and the update all read them from VMEM.

The model row is flat, ``[w (d lanes) | b (lane d)]``, as the tick
kernels hold it; the sample row carries its label in lane d, where the
model has its bias.  The gradient is ``jax.vjp`` of
``logreg.bce_with_logits`` in the logit, times the row with lane d read
as the bias feature 1, plus the l2 term on the ``w`` lanes — autodiff's
expressions for ``logreg.per_example_loss``; the logit adds the bias
as one more term of its lane sum.  Within a step the tile is swept in
groups of ``SUB`` rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.models import logreg

# C tiles are independent; the step axis revisits the resident w / U
# blocks, so it runs in order
_GRID = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))

# rows of a tile swept at once: 16 sublane groups, enough independent
# vector work to hide the per-row chain (logit sum -> gradient scale ->
# clip norm); on a v5e the 2^14-client, 64-step block took 2.3x as long
# sweeping 8 rows as 32, and 6% less at 128 than at 32
SUB = 128


def _sgd_step(d: int, l2: float, clip: float):
    """The masked SGD step of a group of clients, for the model width,
    l2 and clip bound of the block: ``step(x, w, u, n, eta, j)`` with
    x, w, u (rows, D) and n, eta (rows, 1) -> (w, u)."""

    def step(x, w, u, n, eta, j):
        feat = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < d
        xb = jnp.where(feat, x, 1.0)        # lane d: the bias feature
        z = jnp.sum(xb * w, axis=1, keepdims=True)
        _, vjp = jax.vjp(
            lambda z_: logreg.bce_with_logits(z_, x[:, d:d + 1]), z)
        gz, = vjp(jnp.ones_like(z))
        g = gz * xb
        if l2 > 0.0:
            g = jnp.where(feat, jnp.float32(0.5 * l2) * (2.0 * w) + g, g)
        if clip > 0.0:
            norm = jnp.sqrt(jnp.sum(g * g, axis=1, keepdims=True))
            g = g * (1.0 / jnp.maximum(1.0, norm / clip))
        g = (j < n).astype(jnp.float32) * g
        return w - eta * g, u + g

    return step


def _client_block_kernel(x_ref, w_ref, u_ref, n_ref, eta_ref, w_out, u_out,
                         *, step, sub: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _load():
        w_out[...] = w_ref[...]
        u_out[...] = u_ref[...]

    def group(r, carry):
        rows = pl.ds(pl.multiple_of(r * sub, sub), sub)
        w, u = step(x_ref[rows, :], w_out[rows, :], u_out[rows, :],
                    n_ref[rows, :], eta_ref[rows, :], j)
        w_out[rows, :] = w
        u_out[rows, :] = u
        return carry

    jax.lax.fori_loop(0, w_out.shape[0] // sub, group, 0)


def client_block_kernel(rows, w, U, n, eta, *, d: int, l2: float,
                        clip: float, c_block: int, interpret: bool = True):
    """rows: (block, C, D) gathered ``X_aug`` rows, step-major;
    w, U: (C, D); n: (C, 1) int32; eta: (C, 1).  C % c_block == 0, and
    c_block is a multiple of SUB or at most SUB (one sweep).

    Returns (w_new (C, D), U_new (C, D)).
    """
    block, C, D = rows.shape
    sub = min(SUB, c_block)
    assert C % c_block == 0 and c_block % sub == 0, (C, c_block)
    state = pl.BlockSpec((c_block, D), lambda c, j: (c, 0))
    col = pl.BlockSpec((c_block, 1), lambda c, j: (c, 0))
    return pl.pallas_call(
        functools.partial(_client_block_kernel, step=_sgd_step(d, l2, clip),
                          sub=sub),
        name="client_block_sgd",
        grid=(C // c_block, block),
        in_specs=[pl.BlockSpec((None, c_block, D), lambda c, j: (j, c, 0)),
                  state, state, col, col],
        out_specs=[state, state],
        out_shape=[jax.ShapeDtypeStruct((C, D), jnp.float32),
                   jax.ShapeDtypeStruct((C, D), jnp.float32)],
        compiler_params=_GRID,
        # in place: a tile's w / U are read at its first step and written
        # after its last, and tiles are disjoint.  Without the aliases a
        # caller that may pass w / U through unchanged (the segment's
        # lax.cond) copies both before the call
        input_output_aliases={1: 0, 2: 1},
        interpret=interpret,
    )(rows, w, U, n, eta)
