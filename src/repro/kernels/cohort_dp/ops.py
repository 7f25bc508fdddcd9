"""Jitted wrapper for the cohort clip+noise kernel, with padding.

``cohort_clip_noise`` pads the client axis to the client tile and the
model axis to the lane tile (``repro.kernels.tiling``), generates the
Gaussian noise (operand path) or derives an in-kernel PRNG seed (TPU
path) from a jax key, and unpads.  Padded rows carry mask 0 / weight 0,
so they pass through as zeros and contribute nothing to the aggregate.
With a ``mesh`` the kernel runs per client shard and the shards'
aggregates are summed (``psum`` over ``clients``); the operand noise is
drawn over the whole (C, D) block first, so it does not depend on the
sharding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.cohort_dp.kernel import (cohort_clip_noise_kernel,
                                            cohort_clip_noise_prng_kernel)
from repro.kernels.cohort_dp.ref import cohort_clip_noise_ref
from repro.kernels.tiling import CLIENTS, pad_to, per_client_shards, tiles


def _local(u, noise, seed, wgt, mask, *, clip, noise_scale, d_block,
           interpret, in_kernel_rng, sharded):
    C, D = u.shape
    c_block, Cp, d_block, Dp = tiles(C, D, d_block=d_block,
                                     interpret=interpret)
    u = pad_to(u, Cp, Dp)
    wgt = pad_to(wgt[:, None], Cp, 1)
    mask = pad_to(mask[:, None], Cp, 1)
    if in_kernel_rng:
        shard = (jax.lax.axis_index(CLIENTS) if sharded
                 else jnp.int32(0))
        out, agg = cohort_clip_noise_prng_kernel(
            u, jnp.stack([seed, shard]).astype(jnp.int32), wgt, mask,
            clip=clip, noise_scale=noise_scale, c_block=c_block,
            d_block=d_block)
    else:
        out, agg = cohort_clip_noise_kernel(
            u, pad_to(noise, Cp, Dp), wgt, mask, clip=clip,
            noise_scale=noise_scale, c_block=c_block, d_block=d_block,
            interpret=interpret)
    agg = agg[0, :D]
    if sharded:
        with jax.named_scope("cohort.allreduce"):
            agg = jax.lax.psum(agg, CLIENTS)
    return out[:C, :D], agg


@functools.partial(jax.jit, static_argnames=("clip", "noise_scale",
                                             "d_block", "use_kernel",
                                             "interpret", "in_kernel_rng",
                                             "mesh"))
def cohort_clip_noise(u, key, weights, mask, *, clip: float = 0.0,
                      noise_scale: float = 0.0, d_block: int = 128,
                      use_kernel: bool = True, interpret=None,
                      in_kernel_rng: bool = False, mesh=None):
    """u: (C, D) round updates -> (noised rows (C, D), weighted agg (D,)).

    clip <= 0 disables the per-row norm clip (example-granularity DP clips
    inside the iteration loop instead); noise_scale is the std-dev
    multiplier on the standard-normal draw (protocol: dp_clip * dp_sigma).
    With ``in_kernel_rng`` the noise is drawn inside the kernel (TPU only,
    distributionally equivalent but not bit-matching the operand path).
    ``interpret=None`` infers interpret mode from ``jax.default_backend()``
    — interpret on CPU (byte-identical to the historical default there),
    the compiled kernel on a real TPU/GPU.
    """
    C, D = u.shape
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    u = u.astype(jnp.float32)
    mask_f = mask.astype(jnp.float32)
    wgt = weights.astype(jnp.float32)
    draw_operand_noise = noise_scale > 0.0 and not (use_kernel
                                                    and in_kernel_rng)
    noise = (jax.random.normal(key, (C, D), jnp.float32)
             if draw_operand_noise else jnp.zeros((C, D), jnp.float32))
    # the wrapper's scope (repro.telemetry.scopes): the clip+noise with
    # its pads and slices; the operand noise draw above stays outside
    with jax.named_scope("cohort_clip_noise"):
        if not use_kernel:
            return cohort_clip_noise_ref(u, noise, wgt, mask_f, clip=clip,
                                         noise_scale=noise_scale)
        seed = (jax.random.randint(key, (), 0, jnp.iinfo(jnp.int32).max,
                                   jnp.int32) if in_kernel_rng
                else jnp.int32(0))
        cl, row = P(CLIENTS, None), P(CLIENTS)
        local = functools.partial(
            _local, clip=clip, noise_scale=noise_scale, d_block=d_block,
            interpret=interpret,
            in_kernel_rng=in_kernel_rng, sharded=mesh is not None)
        return per_client_shards(local, mesh, (cl, cl, P(), row, row),
                                 (cl, P()))(u, noise, seed, wgt, mask_f)
