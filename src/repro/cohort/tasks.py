"""Batched (cohort) task adapters: per-round client compute with a leading
client axis.

A cohort task exposes the same round computation as ``repro.core.tasks``
but over flat ``[C, D]`` state blocks, advanced for the *whole population*
in one jitted call (``run_block``): a ``vmap``-of-``scan``, or off-CPU
the logreg task's fused client-block kernel.  Per-iteration
sample draws are addressed by ``(client, round, iteration)`` via
``fold_in`` — the same derivation ``LogRegTask`` uses in its
``sample_seed`` mode — so a cohort trajectory is bit-reproducible against
the event simulator regardless of how either engine chunks a round.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core.tasks import BatchModelTask, LogRegTask
from repro.kernels.client_block import client_block_ref, client_block_sgd


class CohortLogRegTask:
    """Whole-population view of ``LogRegTask`` (the paper's experiments)."""

    #: compiled block fns kept, LRU (the cache was unbounded — a
    #: long-lived task accumulated one jit per distinct block size).
    #: The host engine requests next_pow2(nmax) <= next_pow2(2 * block)
    #: — log2(2 * block) + 1 distinct sizes — so 16 covers every block
    #: the engines accept without thrash; LRU keeps recurring sizes hot.
    MAX_BLOCK_FNS = 16

    def __init__(self, task: LogRegTask, n_clients: int, *, seed: int = 0):
        self.task = task
        self.C = int(n_clients)
        self.d_feat = task.d
        self.D = task.d + 1                     # w (d) then b (1), flat
        base_seed = task.sample_seed if task.sample_seed is not None \
            else seed
        base = jax.random.PRNGKey(base_seed)
        self.base_keys = jax.vmap(
            lambda c: jax.random.fold_in(base, c))(jnp.arange(self.C))
        self._block_fns: Dict[int, Any] = {}

    @functools.cached_property
    def X_aug(self):
        """The data set with each row's label in its last column, where
        the flat model has its bias: the client-block kernel's rows.
        Built on the kernel path's first use (the reference reads ``X``
        and ``y``), a second copy of the data set beside ``task.X``."""
        return jnp.concatenate([self.task.X, self.task.y[:, None]], axis=1)

    # -- flat layout -------------------------------------------------------
    def flatten(self, m):
        return jnp.concatenate([m["w"].astype(jnp.float32),
                                m["b"].astype(jnp.float32)[None]])

    def unflatten(self, vec):
        return {"w": vec[:self.d_feat], "b": vec[self.d_feat]}

    def init_flat(self):
        return self.flatten(self.task.init_model())

    def metrics(self, vec) -> Dict[str, float]:
        return self.task.metrics(self.unflatten(vec))

    # -- batched compute ---------------------------------------------------
    def run_block(self, w, U, i, h, n, eta, block: int):
        """Advance every client by up to ``block`` local SGD iterations.

        w, U: [C, D] device blocks; i, h, n: [C] int32 (round, in-round
        offset, iterations to take this call); eta: [C] f32 round step
        sizes.  Steps j >= n[c] are masked no-ops, so one compiled block
        size serves heterogeneous per-client counts.
        """
        fn = self._block_fns.pop(block, None)   # pop+reinsert: LRU order
        if fn is None:
            fn = jax.jit(self.block_body(block))
        self._block_fns[block] = fn
        while len(self._block_fns) > self.MAX_BLOCK_FNS:
            self._block_fns.pop(next(iter(self._block_fns)))
        return fn(w, U, i, h, n, eta)

    def block_body(self, block: int, *, use_kernel=None, mesh=None):
        """The ``run_block`` computation, un-jitted.

        The device-resident engine embeds this directly into its jitted
        tick function (`repro.cohort.device`), where an extra jit wrapper
        would only add trace indirection; host callers go through
        ``run_block``, which jits and caches per block size.

        ``use_kernel=None`` follows the backend, as the tick kernels do:
        the fused client-block kernel (``repro.kernels.client_block``,
        on ``X_aug``) off-CPU, the jnp reference (on ``X``, ``y``) on
        CPU.  ``mesh``: the client mesh when C is sharded; the kernel
        then runs per shard.
        """
        if use_kernel is None:
            use_kernel = jax.default_backend() != "cpu"
        X, y, l2 = self.task.X, self.task.y, self.task.l2
        clip, n_data = self.task.dp_clip, self.task.X.shape[0]
        X_aug = self.X_aug if use_kernel else None
        base_keys = self.base_keys

        def sample_idx(i, h):
            """[C, block] indices, LogRegTask.sample_indices' derivation:
            one threefry per (client, round, iteration), index = first
            key word mod n.  Batched OUTSIDE the SGD scan: per-step
            hashing inside the scan serializes block tiny dispatches and
            was ~2/3 of run_block wall time at C=4096."""
            round_keys = jax.vmap(jax.random.fold_in)(base_keys, i)

            def one(rk_c, h_c):
                ks = jax.vmap(lambda j: jax.random.fold_in(rk_c, h_c + j))(
                    jnp.arange(block))
                return (ks[:, 0] % jnp.uint32(n_data)).astype(jnp.int32)

            return jax.vmap(one)(round_keys, h)

        def run(w, U, i, h, n, eta):
            idx = sample_idx(i, h)
            if use_kernel:
                return client_block_sgd(w, U, idx.T, n, eta, X_aug, l2=l2,
                                        clip=clip, mesh=mesh)
            return client_block_ref(w, U, idx, n, eta, X, y, l2=l2,
                                    clip=clip)

        return run


def as_cohort_task(task, n_clients: int, *, seed: int = 0):
    """Adapt a ``repro.core.tasks`` task (or pass through a cohort task)."""
    if hasattr(task, "run_block"):
        return task
    if isinstance(task, LogRegTask):
        return CohortLogRegTask(task, n_clients, seed=seed)
    if isinstance(task, BatchModelTask):
        from repro.cohort.flat import CohortBatchModelTask
        return CohortBatchModelTask(task, n_clients, seed=seed)
    raise TypeError(f"no cohort adapter for {type(task).__name__}; "
                    "provide an object with run_block/init_flat/metrics")
