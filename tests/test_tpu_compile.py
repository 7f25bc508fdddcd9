"""Compile-only tests of the device engine's Pallas kernels for a TPU v5e.

The TPU compiler is installed beside JAX, and it compiles for a chip that
is described rather than attached (``jax.experimental.topologies``).
These tests hand it the kernels of the device tick at the fleet and model
sizes the chip runs (C in {64, 4096}, D in {33, 785}, and D = 4096 for
the tiling of server rows; the client block also at the benchmark's
fleets, 2^14 clients by 64 steps and 2^18 by one) and assert that
Mosaic accepts each one, that it fits scoped VMEM, and that the
compiled program calls the kernel; for the client block, also that its
gathered rows stay within their HBM budget.
Interpret mode, which the CPU tests use, checks neither layout nor VMEM.
Nothing runs, so nothing here says anything about results or speed.

The topology is described inside a module fixture, never at import: the
TPU library admits one process at a time, and every test worker imports
this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels.client_block import client_block_sgd
from repro.kernels.cohort_dp import cohort_clip_noise
from repro.kernels.tick_fused import bucket_apply, tick_deliver, tick_scatter
from repro.telemetry.costs import collectives_in, pallas_kernels_in

B, G, A = 4, 4, 4      # broadcast slots, scatter rows, stratified rows
SIZES = [(64, 33), (64, 785), (4096, 33), (4096, 785)]


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # libtpu is pinned in requirements.txt: a missing compiler fails
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return spec


@pytest.mark.parametrize("C,D", SIZES + [(4096, 4096)])
def test_tick_deliver_compiles(one_chip, no_compile_cache, C, D):
    S = _spec(one_chip)
    text = _compile(
        lambda *a: tick_deliver(*a, use_kernel=True, interpret=False),
        S((C, D)), S((C, D)), S((B, D)), S((C,), jnp.int32),
        S((C,), jnp.bool_), S((C,)))
    assert "tick_deliver" in pallas_kernels_in(text)


@pytest.mark.parametrize("dp_on", [False, True])
@pytest.mark.parametrize("C,D", SIZES + [(4096, 4096)])
def test_tick_scatter_compiles(one_chip, no_compile_cache, C, D, dp_on):
    S = _spec(one_chip)
    text = _compile(
        lambda *a: tick_scatter(*a, dp_on=dp_on, use_kernel=True,
                                interpret=False),
        S((C, D)), S((C, D)), S((C, D)), S((G, D)), S((G, C)),
        S((G,), jnp.bool_), S((C,), jnp.bool_), S((C,)))
    assert "tick_scatter" in pallas_kernels_in(text)


@pytest.mark.parametrize("rows", [1, A])
@pytest.mark.parametrize("D", [33, 785, 4096])
def test_bucket_apply_compiles(one_chip, no_compile_cache, D, rows):
    S = _spec(one_chip)
    text = _compile(
        lambda *a: bucket_apply(*a, use_kernel=True, interpret=False),
        S((D,)), S((rows, D)), S((rows,)), S((), jnp.bool_))
    assert "bucket_apply" in pallas_kernels_in(text)


@pytest.mark.parametrize("in_kernel_rng", [False, True])
@pytest.mark.parametrize("C,D", SIZES + [(64, 4096)])
def test_cohort_clip_noise_compiles(one_chip, no_compile_cache, C, D,
                                    in_kernel_rng):
    S = _spec(one_chip)
    text = _compile(
        lambda *a: cohort_clip_noise(
            *a, clip=0.1, noise_scale=0.8, use_kernel=True,
            interpret=False, in_kernel_rng=in_kernel_rng),
        S((C, D)), S((2,), jnp.uint32), S((C,)), S((C,), jnp.bool_))
    want = "cohort_clip_noise_prng" if in_kernel_rng else "cohort_clip_noise"
    assert {want, "row_sqsum"} <= pallas_kernels_in(text)


def test_sharded_scatter_reduces_ring_rows_only(topo, no_compile_cache):
    """Over four chips the client kernels run per shard (shard_map), and
    the one float collective is the all-reduce of the [G, D] ring sums —
    the server bucket reduce."""
    mesh = Mesh(topo.devices, ("clients",))
    C, D = 4096, 785
    cl = NamedSharding(mesh, P("clients", None))
    row = NamedSharding(mesh, P("clients"))
    rep = NamedSharding(mesh, P())
    text = _compile(
        lambda *a: tick_scatter(*a, dp_on=True, use_kernel=True,
                                interpret=False, mesh=mesh),
        *(jax.ShapeDtypeStruct((C, D), jnp.float32, sharding=cl)
          for _ in range(3)),
        jax.ShapeDtypeStruct((G, D), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((G, C), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, "clients"))),
        jax.ShapeDtypeStruct((G,), jnp.bool_, sharding=rep),
        jax.ShapeDtypeStruct((C,), jnp.bool_, sharding=row),
        jax.ShapeDtypeStruct((C,), jnp.float32, sharding=row))
    assert "tick_scatter" in pallas_kernels_in(text)
    colls = collectives_in(text)
    assert colls and all(kind == "all-reduce"
                         and shape.startswith(f"f32[{G},{D}]")
                         for shape, kind in colls), colls


@pytest.mark.parametrize("C,D,block", [(64, 33, 1), (4096, 785, 64),
                                       (16384, 785, 64), (262144, 785, 1)])
def test_client_block_compiles(one_chip, no_compile_cache, C, D, block):
    """The fused client block at the benchmark's fleets.  ``w`` and ``U``
    enter and leave row-major, as the segment's loop carries them: the
    block adds no transpose of the client state (the ``{0,1}`` layout a
    ``[C, 785]`` array takes by default on the chip) and no copy."""
    S = _spec(one_chip)
    rm = Format(Layout(major_to_minor=(0, 1)), one_chip)
    state = jax.ShapeDtypeStruct((C, D), jnp.float32, sharding=rm)

    def tick(w, U, idx, n, eta, X_aug, any_block):
        # as the segment calls it: skipped (w, U passed through) on a
        # tick where no client steps
        return jax.lax.cond(
            any_block,
            lambda ops: client_block_sgd(*ops, l2=1e-4, clip=0.1,
                                         interpret=False),
            lambda ops: (ops[0], ops[1]), (w, U, idx, n, eta, X_aug))

    # X_aug row-major, as the segment holds its constant
    X_aug = jax.ShapeDtypeStruct((60000, D), jnp.float32, sharding=rm)
    compiled = jax.jit(tick, donate_argnums=(0, 1),
                       out_shardings=(rm, rm)).lower(
        state, state, S((block, C), jnp.int32), S((C,), jnp.int32),
        S((C,)), X_aug, S((), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "client_block_sgd" in pallas_kernels_in(text)
    # the block's temporaries are its gathered rows, at most 1 GiB
    # (ROW_SLAB_BYTES; one step's where those alone exceed it: 0.94 GB
    # at 2^18 clients), and the kernel's (C, 1) columns n and eta, each
    # padded to 128 lanes; a gather of the whole 64-step block at 2^14
    # clients would hold 3.76 GB
    lanes = -(-D // 128) * 128
    bound = (max(1 << 30, C * lanes * 4) + 2 * C * 128 * 4
             + (8 << 20))
    assert compiled.memory_analysis().temp_size_in_bytes <= bound
    # the kernel updates w and U in place: no copy of either, in the
    # chip's default {0,1} layout or in the row-major one
    assert not re.findall(rf"f32\[{C},{D}\]\{{[^}}]*\}} copy\(", text)
