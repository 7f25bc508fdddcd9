"""Block sizes and padding shared by the [C, D] client-block kernels.

Mosaic (the TPU kernel compiler) wants every block's last dim a multiple
of 128 lanes and its second-last a multiple of 8 sublanes, unless the
block spans the whole array dim.  Per-client scalars and masks therefore
enter the kernels as ``(C, 1)`` columns and server vectors as ``(1, D)``
rows; no kernel takes a 1-D operand.

On the compiled path the client axis is tiled too, so one block stays
near ``TILE_ELEMS`` f32 elements (512 KiB) whatever C is: the
double-buffered operands of the widest kernel (the ring scatter, five
[C, D] blocks) then fit the 16 MiB of scoped VMEM at any fleet size.
Reductions over clients add within a C tile first, then across tiles in
ascending order.  In interpret mode (the CPU path) C is one tile, so
every sum keeps the full-axis order of the pure-jnp references.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CLIENTS = "clients"          # the client mesh axis (repro.sharding)
LANES = 128
SUBLANES = 8
TILE_ELEMS = 128 * 1024


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def tiles(C: int, D: int, *, d_block: int, interpret: bool):
    """Return ``(c_block, Cp, d_block, Dp)``: block sizes and the padded
    dims they divide.

    Interpret mode has no lane constraint: the D tile shrinks to the
    model dim's power of two (min 8) so a small D is not padded many
    times over, and C stays one tile.
    Compiled: D pads to the lane multiple, the D tile is the largest of
    ``d_block``, its halves, ... down to 128 that divides it, and the C
    tile fills the ``TILE_ELEMS`` budget.
    """
    if interpret:
        d_block = min(d_block, max(SUBLANES, 1 << (D - 1).bit_length()))
        Dp = round_up(D, d_block)
    else:
        Dp = round_up(D, LANES)
        d_block = max(d_block, LANES)
        while Dp % d_block:
            d_block //= 2
    c_block = (round_up(C, SUBLANES) if interpret
               else min(round_up(C, SUBLANES), TILE_ELEMS // d_block))
    return c_block, round_up(C, c_block), d_block, Dp


def pad_to(x, *shape):
    """Zero-pad ``x`` up to ``shape`` (no copy when already there)."""
    pads = [(0, n - s) for s, n in zip(x.shape, shape)]
    if any(p for _, p in pads):
        x = jnp.pad(x, pads)
    return x


def per_client_shards(fn, mesh, in_specs, out_specs):
    """Run ``fn`` on each client shard of ``mesh`` (as-is when None): a
    ``pallas_call`` cannot be partitioned by the compiler."""
    if mesh is None:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
