"""Flat-params cohort adapter: any pytree task as a ``[C, D]`` block task.

``CohortLogRegTask`` hand-flattens its two leaves (w, b); model-scale
tasks (``BatchModelTask``) carry an arbitrary parameter pytree, so the
cohort engines need a generic ravel/unravel with a fixed memory layout.
Two pieces:

* ``PyTreeFlattener`` — records a template's treedef + leaf shapes +
  dtypes once, then maps pytree <-> flat ``[D]`` f32 vector with static
  offsets (jit-traceable both ways).  Accumulation happens in f32; leaf
  dtypes of 32 bits or fewer (f32/bf16/f16) round-trip **bit-exactly**
  because f32 is a superset of their value sets.

* ``CohortBatchModelTask`` — the whole-population view of a
  ``BatchModelTask``: ``block_body`` embeds the minibatch
  forward/backward, optional update clip, and update-accumulate inside
  the vmapped scan the cohort engines drive, over flat ``[C, D]`` blocks.
  Per-(client, round, iteration) batches are addressed by the same
  ``fold_in`` chain ``CohortLogRegTask.sample_idx`` uses —
  ``fold_in(fold_in(fold_in(base, client), round), h + j)`` — so a cohort
  trajectory is reproducible against the event simulator driving the
  *same* ``BatchModelTask`` through a ``SeedAddressedBatcher``
  (``repro.data.federated``), regardless of how either engine chunks a
  round.

Memory model: D is the width of what the clients train, the task's
template.  For full training that is the whole model; for a task with a
``frozen`` base (LoRA adapters, ``repro.models.lora``) it is the adapters
alone, and the base is held once, in its own dtype, for every client.
The engines hold the population as one ``[C, D]`` f32 residency for
models plus one for update accumulators (2 * C * D * 4 bytes), sharded
over local devices via ``repro.sharding.cohort_*``; the server keeps a
few ``[D]`` rows besides (``v``, the arrival buckets, the broadcast
ring), and the vmapped step its activations.  Choose C and D so these
fit, and keep ``block`` small (a model-scale "iteration" is a full
minibatch step, so a handful of iterations per round is the
Bonawitz-style regime).

The frozen base and the clients' base keys reach the compiled block as
one operand (``operands``), which the device engine passes into its
segment unchanged: nothing large is a constant of the executable.
Without DP the segment does not depend on the seed either: its lowered
module is the same for every seed.  With DP on, the segment's noise key
is drawn from the seed as a constant, and each seed compiles its own.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.tasks import BatchModelTask, clip_tree


class PyTreeFlattener:
    """Static pytree <-> flat f32 vector codec (shapes fixed at init).

    ``flatten`` ravels every leaf to f32 and concatenates in treedef
    order; ``unflatten`` slices at the recorded static offsets, reshapes,
    and casts back to each leaf's original dtype.  Both directions are
    pure jnp with static indices, so they trace inside jit/vmap/scan.
    """

    def __init__(self, template):
        leaves, self.treedef = jax.tree_util.tree_flatten(template)
        if not leaves:
            raise ValueError("PyTreeFlattener needs a template with at "
                             "least one array leaf")
        self.shapes: Tuple[Tuple[int, ...], ...] = tuple(
            tuple(l.shape) for l in leaves)
        self.dtypes = tuple(jnp.asarray(l).dtype for l in leaves)
        for dt in self.dtypes:
            # enforce the exactness contract up front: int/bool leaves
            # (and >32-bit floats) would silently corrupt through the
            # f32 round trip (e.g. int32 values above 2**24)
            if not (jnp.issubdtype(dt, jnp.floating)
                    and jnp.dtype(dt).itemsize <= 4):
                raise TypeError(
                    f"PyTreeFlattener leaves must be <=32-bit floats "
                    f"(f32/bf16/f16) for an exact f32 round trip; got "
                    f"{jnp.dtype(dt).name}")
        self.sizes = tuple(int(math.prod(s)) for s in self.shapes)
        offs, o = [], 0
        for s in self.sizes:
            offs.append(o)
            o += s
        self.offsets = tuple(offs)
        self.D = o

    def flatten(self, tree) -> jnp.ndarray:
        """tree -> [D] f32 (f32 is exact for <=32-bit float leaves)."""
        leaves = self.treedef.flatten_up_to(tree)
        return jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32) for l in leaves])

    def unflatten(self, vec, dtype=None):
        """[D] vector -> tree.  ``dtype=None`` restores each leaf's
        template dtype; pass e.g. ``jnp.float32`` to keep accumulator
        trees in f32 regardless of the template."""
        leaves = [
            jnp.reshape(vec[o:o + s], shape).astype(dtype or dt)
            for o, s, shape, dt in zip(self.offsets, self.sizes,
                                       self.shapes, self.dtypes)]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


class CohortBatchModelTask:
    """Whole-population view of ``BatchModelTask`` (model-scale rounds).

    Mirrors the ``CohortLogRegTask`` interface (``run_block`` /
    ``block_body`` / ``init_flat`` / ``metrics``) so both cohort engines
    drive it unchanged; one local iteration is one minibatch-SGD step on
    the task's architecture.  Requires the task's ``data_fn`` to be
    seed-addressed (``batch_from_key``; see
    ``repro.data.federated.SeedAddressedBatcher``) — a host-callable
    batcher cannot produce batches inside the vmapped scan, and a
    stream-addressed one would break event-simulator reproducibility.
    """

    #: compiled block fns kept per task, LRU — must cover every
    #: power-of-two the host engine can request (next_pow2(nmax) <=
    #: next_pow2(2 * block)), or a recurring size would recompile a
    #: model-sized jit every few ticks (see CohortLogRegTask)
    MAX_BLOCK_FNS = 16

    def __init__(self, task: BatchModelTask, n_clients: int, *,
                 seed: int = 0):
        batcher = task.data_fn
        if not hasattr(batcher, "batch_from_key"):
            raise TypeError(
                "CohortBatchModelTask needs a seed-addressed batcher "
                "(data_fn with a batch_from_key method, e.g. "
                "repro.data.SeedAddressedBatcher); a host-callable "
                f"batcher like {type(batcher).__name__} cannot run "
                "inside the vmapped block")
        self.task = task
        self.C = int(n_clients)
        self.flattener = PyTreeFlattener(task.template)
        self.D = self.flattener.D
        # batch addressing shares the batcher's base key, so the event
        # simulator (task.data_fn(c, i, h)) and the cohort block draw the
        # SAME batch for the same (client, round, iteration)
        self.base_keys = jax.vmap(
            lambda c: jax.random.fold_in(batcher.base, c))(
                jnp.arange(self.C))
        # tokens of one client's minibatch: the device engine counts
        # ``client_tokens`` by it
        self.tokens_per_step = int(batcher.batch_size * batcher.seq_len)
        self._block_fns: Dict[int, Any] = {}

    @property
    def operands(self) -> Dict[str, Any]:
        """What the compiled block reads besides the client state: the
        frozen base (None without one) and the clients' base keys."""
        return {"frozen": self.task.frozen, "keys": self.base_keys}

    # -- flat layout -------------------------------------------------------
    def flatten(self, tree):
        return self.flattener.flatten(tree)

    def unflatten(self, vec):
        return self.flattener.unflatten(vec)

    def init_flat(self):
        return self.flattener.flatten(self.task.init_model())

    def metrics(self, vec) -> Dict[str, float]:
        return self.task.metrics(self.flattener.unflatten(vec))

    # -- batched compute ---------------------------------------------------
    def run_block(self, w, U, i, h, n, eta, block: int):
        """Advance every client by up to ``block`` minibatch steps.

        Same contract as ``CohortLogRegTask.run_block``: w, U are [C, D]
        blocks, i/h/n are [C] int32, eta is [C] f32, and steps j >= n[c]
        are masked no-ops.
        """
        fn = self._block_fns.pop(block, None)   # pop+reinsert: LRU order
        if fn is None:
            fn = jax.jit(self.block_body(block))
        self._block_fns[block] = fn
        while len(self._block_fns) > self.MAX_BLOCK_FNS:
            self._block_fns.pop(next(iter(self._block_fns)))
        return fn(w, U, i, h, n, eta, self.operands)

    def block_body(self, block: int, *, mesh=None):
        """The ``run_block`` computation, un-jitted (the device engine
        embeds it directly in its jitted tick; see
        ``CohortLogRegTask.block_body``): ``run(w, U, i, h, n, eta,
        operands)``, ``operands`` the task's ``operands``.  Plain XLA,
        which the compiler partitions over a client ``mesh`` itself."""
        task = self.task
        clip = task.dp_clip
        batch_from_key = task.data_fn.batch_from_key
        flt = self.flattener

        def per_client(w_c, U_c, rk_c, h_c, n_c, eta_c, frozen):
            params = flt.unflatten(w_c)
            upd = flt.unflatten(U_c, dtype=jnp.float32)

            def body(carry, j):
                p, u = carry
                batch = batch_from_key(
                    jax.random.fold_in(rk_c, h_c + j))
                g = jax.grad(task.loss)(p, batch, frozen)
                if clip > 0.0:
                    g = clip_tree(g, clip)
                act = (j < n_c).astype(jnp.float32)
                g = jax.tree_util.tree_map(lambda l: act * l, g)
                u = jax.tree_util.tree_map(jnp.add, u, g)
                # cast back to the leaf dtype: keeps the scan carry
                # stable for sub-f32 templates (identity for f32, where
                # trajectories are event-engine-exact)
                p = jax.tree_util.tree_map(
                    lambda a, gg: (a - eta_c * gg).astype(a.dtype), p, g)
                return (p, u), None

            (params, upd), _ = jax.lax.scan(body, (params, upd),
                                            jnp.arange(block))
            return flt.flatten(params), flt.flatten(upd)

        def run(w, U, i, h, n, eta, operands):
            # one threefry per (client, round) hoisted out of the scan,
            # exactly CohortLogRegTask.sample_idx's derivation
            round_keys = jax.vmap(jax.random.fold_in)(operands["keys"], i)
            return jax.vmap(per_client, (0, 0, 0, 0, 0, 0, None))(
                w, U, round_keys, h, n, eta, operands["frozen"])

        return run
