"""Jitted wrapper for the fused client-block kernel: the sample-row
gather, padding, and per-shard dispatch.

``client_block_sgd`` gathers the block's sample rows from ``X_aug`` in
XLA, step-major (``[k, C, D]``, so a step's rows are one ``(C, D)``
slab under the (8, 128) tiling), and hands them to the kernel with the
client state.  The gather stays in XLA: a DMA gather inside the kernel
needs a tile-aligned row (Mosaic slices the (8, 128)-tiled ``X_aug`` by
whole sublane groups only), and with each row padded to its own tile
the per-row copies, issued and awaited one client at a time, ran the
2^14-client, 64-step block in 57-63 ms on a v5e against 29 ms for this
gather and the kernel.

The gathered rows are bounded: the block runs in chunks of ``k`` steps,
``k`` the largest divisor of the block whose ``[k, C, D]`` rows fit
``ROW_SLAB_BYTES`` (one step when a single step's rows do not).  Each
chunk gathers its rows and calls the kernel, which takes ``w`` and
``U`` in place, so a chunk costs one more read and write of the state;
the block's rows never exceed ``max(ROW_SLAB_BYTES, one step's rows)``,
where the reference's scan held one step's rows.  Chunking changes no
value: the state passes between chunks in f32, as between steps.

C pads to the client tile: padded clients take no step (n = 0) and are
sliced off.  D is never padded — each block spans the whole model row —
so ``w`` and ``U`` go in and come out in their own row-major layout,
updated in place.

The kernel's contract against the reference (``ref.py``): the row
gather and every mask exact; the logit dot and the clip norm add over D
in the kernel's order, so each differs from the reference by at most
its reduction-order bound (README parity table); the other expressions
are the reference's.

``mesh``: when the engine shards the client axis, the kernel runs per
client shard under ``shard_map`` (a ``pallas_call`` cannot be
partitioned by the compiler).  The block is per client: no collective.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.client_block.kernel import client_block_kernel
from repro.kernels.tiling import (CLIENTS, LANES, SUBLANES, TILE_ELEMS,
                                  pad_to, per_client_shards, round_up)

#: bytes of gathered sample rows a chunk of the block may hold in HBM
#: (per client shard): 1 GiB, 16 steps of a 2^14-client fleet at D = 785
ROW_SLAB_BYTES = 1 << 30


def client_tile(C: int, D: int) -> int:
    """Clients per tile: a power of two (so it divides a power-of-two
    fleet) whose state block holds at most ``2 * TILE_ELEMS`` f32
    elements, C itself (to the sublane) when smaller.  The kernel keeps
    ten such blocks in VMEM (rows, w and U in, w and U out, each double
    buffered): 9.2 MB at D = 785, inside the 16 MiB scoped limit (512
    clients exceed it)."""
    lanes = round_up(D, LANES)
    c_block = SUBLANES
    while 2 * c_block * lanes <= 2 * TILE_ELEMS:
        c_block *= 2
    return min(round_up(C, SUBLANES), c_block)


def steps_per_gather(block: int, C: int, D: int) -> int:
    """Steps whose rows one gather takes: the largest divisor of
    ``block`` whose ``[k, C, D]`` f32 rows, lanes padded to 128, fit
    ``ROW_SLAB_BYTES``; at least one."""
    step_bytes = C * round_up(D, LANES) * 4
    k = max(1, min(block, ROW_SLAB_BYTES // step_bytes))
    while block % k:
        k -= 1
    return k


def _local(w, U, idx, n, eta, X_aug, *, l2, clip, interpret):
    C, D = w.shape
    block = idx.shape[0]
    c_block = client_tile(C, D)
    Cp = round_up(C, c_block)
    k = steps_per_gather(block, Cp, D)
    idx = pad_to(idx, block, Cp)
    n = pad_to(n.astype(jnp.int32)[:, None], Cp, 1)
    eta = pad_to(eta.astype(jnp.float32)[:, None], Cp, 1)
    kernel = functools.partial(client_block_kernel, d=D - 1, l2=l2,
                               clip=clip, c_block=c_block,
                               interpret=interpret)

    def chunk(s, state):
        # steps s*k .. s*k + k - 1: the kernel's step j is the block's
        # step s*k + j, taken while s*k + j < n
        rows = X_aug[jax.lax.dynamic_slice_in_dim(idx, s * k, k)]
        return tuple(kernel(rows, *state, n - s * k, eta))

    state = (pad_to(w, Cp, D), pad_to(U, Cp, D))
    if k == block:
        state = chunk(0, state)
    else:
        state = jax.lax.fori_loop(0, block // k, chunk, state)
    w_new, u_new = state
    return w_new[:C], u_new[:C]


@functools.partial(jax.jit, static_argnames=("l2", "clip", "interpret",
                                             "mesh"))
def client_block_sgd(w, U, idx, n, eta, X_aug, *, l2: float, clip: float,
                     interpret=None, mesh=None):
    """w, U: (C, D) f32; idx: (block, C) int32 sample rows, step-major;
    n: (C,) int steps to take; eta: (C,); X_aug: (N, D) f32, the data
    set with its label in the last column -> (w_new (C, D), U_new (C, D)).

    ``interpret=None`` follows the backend: interpret mode on CPU, the
    compiled kernel on a TPU.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    cl, row = P(CLIENTS, None), P(CLIENTS)
    local = functools.partial(_local, l2=l2, clip=clip, interpret=interpret)
    with jax.named_scope("client_block_sgd"):
        return per_client_shards(
            local, mesh, (cl, cl, P(None, CLIENTS), row, row, P()),
            (cl, cl))(w.astype(jnp.float32), U.astype(jnp.float32), idx, n,
                      eta, X_aug)
