"""Jitted dispatch wrappers for the fused tick kernels, with padding.

Backend-aware dispatch: ``use_kernel=None`` (the engine default) means
"kernel on TPU/GPU, pure-jnp reference on CPU", and ``interpret=None``
means "infer interpret mode from ``jax.default_backend()``".  On CPU
the reference path therefore traces the exact expressions the device
engine historically inlined, so the golden fixtures and host-vs-device
parity stay byte-identical by construction.  An accelerator backend runs
the compiled kernels, whose contract against the reference is: the
delivery gather and every mask/select exact, each elementwise
multiply-add within one rounding of its product (it may be fused), the
sums (ring scatter, stratified bucket apply) within the reduction-order
bound the README's parity table states.

Kernel-path padding (``repro.kernels.tiling``): C to the client tile, D
to the lane tile.  Padded clients carry weight/mask/take 0 and padded
model lanes are zero, so they are sliced off unchanged.

``mesh``: when the engine shards the client axis over several devices,
the client kernels run per shard under ``shard_map`` — a ``pallas_call``
cannot be partitioned by the compiler — and the ring sums of the
scatter are then summed across shards (``psum`` over ``clients``), the
one float collective of the tick.  The bucket apply touches replicated
server vectors only; under a mesh every device runs it on its copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.tick_fused.kernel import (bucket_apply_kernel,
                                             tick_deliver_kernel,
                                             tick_scatter_kernel)
from repro.kernels.tick_fused.ref import (bucket_apply_ref,
                                          tick_deliver_ref,
                                          tick_scatter_ref)
from repro.kernels.tiling import CLIENTS, pad_to, per_client_shards, tiles


def _resolve(use_kernel, interpret):
    if use_kernel is None:
        use_kernel = jax.default_backend() != "cpu"
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return use_kernel, interpret


def _col(x, Cp, dtype):
    """[C] per-client scalars -> padded (Cp, 1) kernel column."""
    return pad_to(x.astype(dtype)[:, None], Cp, 1)


def _bucket_local(v, rows, dec, flag, *, d_block, interpret):
    A, D = rows.shape
    _, _, d_block, Dp = tiles(1, D, d_block=d_block, interpret=interpret)
    out = bucket_apply_kernel(
        pad_to(v.astype(jnp.float32)[None, :], 1, Dp),
        pad_to(rows.astype(jnp.float32), A, Dp),
        dec.astype(jnp.float32)[:, None],
        jnp.asarray(flag, jnp.int32).reshape((1, 1)),
        d_block=d_block, interpret=interpret)
    return out[0, :D]


@functools.partial(jax.jit, static_argnames=("d_block", "use_kernel",
                                             "interpret", "mesh"))
def bucket_apply(v, rows, dec, flag, *, d_block: int = 512,
                 use_kernel=None, interpret=None, mesh=None):
    """v: (D,), rows: (A, D), dec: (A,), flag: scalar bool -> (D,)."""
    use_kernel, interpret = _resolve(use_kernel, interpret)
    with jax.named_scope("bucket_apply"):
        if not use_kernel:
            return bucket_apply_ref(v, rows, dec, flag)
        # replicated operands: every device applies the same bucket
        local = functools.partial(_bucket_local, d_block=d_block,
                                  interpret=interpret)
        return per_client_shards(local, mesh, (P(),) * 4, P())(
            v, rows, dec, flag)


def _deliver_local(w, U, bc_v, best, take, eta, *, d_block, interpret):
    C, D = w.shape
    B = bc_v.shape[0]
    c_block, Cp, d_block, Dp = tiles(C, D, d_block=d_block,
                                     interpret=interpret)
    out = tick_deliver_kernel(
        pad_to(w.astype(jnp.float32), Cp, Dp),
        pad_to(U.astype(jnp.float32), Cp, Dp),
        pad_to(bc_v.astype(jnp.float32), B, Dp),
        _col(best, Cp, jnp.int32), _col(take, Cp, jnp.int32),
        _col(eta, Cp, jnp.float32),
        c_block=c_block, d_block=d_block, interpret=interpret)
    return out[:C, :D]


@functools.partial(jax.jit, static_argnames=("d_block", "use_kernel",
                                             "interpret", "mesh"))
def tick_deliver(w, U, bc_v, best, take, eta, *, d_block: int = 512,
                 use_kernel=None, interpret=None, mesh=None):
    """w, U: (C, D); bc_v: (B, D); best: (C,) int; take: (C,) bool;
    eta: (C,) -> updated weights (C, D)."""
    use_kernel, interpret = _resolve(use_kernel, interpret)
    with jax.named_scope("tick_deliver"):
        if not use_kernel:
            return tick_deliver_ref(w, U, bc_v, best, take, eta)
        cl, row = P(CLIENTS, None), P(CLIENTS)
        local = functools.partial(_deliver_local, d_block=d_block,
                                  interpret=interpret)
        return per_client_shards(local, mesh,
                                 (cl, cl, P(), row, row, row), cl)(
            w, U, bc_v, best, take, eta)


def _scatter_local(sent, w, U, wgt, done, eta, *, dp_on, d_block,
                   interpret, sharded):
    C, D = sent.shape
    G = wgt.shape[0]
    c_block, Cp, d_block, Dp = tiles(C, D, d_block=d_block,
                                     interpret=interpret)
    w_new, u_new, vec = tick_scatter_kernel(
        pad_to(sent.astype(jnp.float32), Cp, Dp),
        pad_to(w.astype(jnp.float32), Cp, Dp),
        pad_to(U.astype(jnp.float32), Cp, Dp),
        pad_to(wgt.astype(jnp.float32).T, Cp, G),
        _col(done, Cp, jnp.int32), _col(eta, Cp, jnp.float32),
        dp_on=dp_on, c_block=c_block, d_block=d_block,
        interpret=interpret)
    vec = vec[:, :D]
    if sharded:
        # the server bucket reduce: ring-row partial sums across shards
        with jax.named_scope("cohort.allreduce"):
            vec = jax.lax.psum(vec, CLIENTS)
    return w_new[:C, :D], u_new[:C, :D], vec


@functools.partial(jax.jit, static_argnames=("dp_on", "d_block",
                                             "use_kernel", "interpret",
                                             "mesh"))
def tick_scatter(sent, w, U, upd, wgt, any_g, done, eta, *, dp_on: bool,
                 d_block: int = 512, use_kernel=None, interpret=None,
                 mesh=None):
    """sent, w, U: (C, D); upd: (G, D); wgt: (G, C); any_g: (G,) bool;
    done: (C,) bool; eta: (C,)
    -> (w_new (C, D), U_new (C, D), upd_new (G, D))."""
    use_kernel, interpret = _resolve(use_kernel, interpret)
    with jax.named_scope("tick_scatter"):
        if not use_kernel:
            return tick_scatter_ref(sent, w, U, upd, wgt, any_g, done, eta,
                                    dp_on=dp_on)
        cl, row = P(CLIENTS, None), P(CLIENTS)
        local = functools.partial(_scatter_local, dp_on=dp_on,
                                  d_block=d_block, interpret=interpret,
                                  sharded=mesh is not None)
        w_new, u_new, vec = per_client_shards(
            local, mesh, (cl, cl, cl, P(None, CLIENTS), row, row),
            (cl, cl, P()))(sent, w, U, wgt, done, eta)
    # guarded add, outside the wrapper's scope (ring-bucket work, not
    # the kernel's layout): rows with no arrivals stay bitwise untouched
    upd = upd.astype(jnp.float32)
    upd_new = jnp.where((any_g != 0)[:, None], upd + vec, upd)
    return w_new, u_new, upd_new
