"""Task abstraction: the computation a client performs inside a round.

``LogRegTask`` reproduces the paper's experiments: per-iteration
single-sample SGD (Algorithm 1 lines 15-21), optional per-sample gradient
clipping (line 17) and round Gaussian noise (lines 23-24).  Iteration
chunks are jitted per power-of-two length to avoid a compile per distinct
segment length (the event simulator produces many lengths).

``BatchModelTask`` adapts any ``repro.models`` architecture: one "local
iteration" = one minibatch-SGD step (the paper's footnote ‡ licenses batch
SGD per round); DP clips the client's round update (user-level DP).  With
a ``frozen`` base it trains only the template's parameters (LoRA
adapters, ``repro.models.lora``) over that base, held once for every
client.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import logreg


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def clip_tree(tree, clip: float):
    norm = global_norm(tree)
    scale = 1.0 / jnp.maximum(1.0, norm / clip)
    return jax.tree_util.tree_map(lambda l: l * scale, tree)


def validate_dp_knobs(dp_clip: float, dp_sigma: float, who: str) -> None:
    """Round noise is drawn with std dp_clip * dp_sigma (Algorithm 1
    line 23 scales the Gaussian by the clip bound), so dp_sigma > 0 with
    dp_clip == 0 silently produced ZERO noise — no privacy, no error.
    Shared by the tasks and both cohort engines."""
    if dp_sigma > 0.0 and dp_clip <= 0.0:
        raise ValueError(
            f"{who}: dp_sigma={dp_sigma} > 0 requires dp_clip > 0 — the "
            "round-noise std is dp_clip * dp_sigma, so dp_clip == 0 "
            "would add zero noise while appearing to be private")


class LogRegTask:
    """Paper experiment task (strongly-convex / plain-convex logreg).

    ``sample_seed``: when set, the per-iteration sample index is derived
    from ``fold_in(fold_in(fold_in(key(sample_seed), client), round), h)``
    instead of the client's streaming rng — the index is the folded key's
    first word mod n (one threefry application; a ``randint`` on the
    folded key would hash a second time and the derivation dominates the
    SGD block at cohort scale).  The draw then depends only on
    *(client, round, iteration)* — not on how the event simulator happens
    to chunk a round into ``run()`` calls — which makes trajectories
    reproducible across engines (see ``repro.cohort``).
    """

    def __init__(self, X, y, *, l2: float = 0.0, dp_clip: float = 0.0,
                 dp_sigma: float = 0.0, d_features: Optional[int] = None,
                 sample_seed: Optional[int] = None):
        self.X = jnp.asarray(X, jnp.float32)
        self.y = jnp.asarray(y, jnp.float32)
        self.l2 = float(l2)
        self.dp_clip = float(dp_clip)
        self.dp_sigma = float(dp_sigma)
        validate_dp_knobs(self.dp_clip, self.dp_sigma, "LogRegTask")
        self.d = d_features or self.X.shape[1]
        self.sample_seed = sample_seed
        self._chunk_fns: Dict[int, Any] = {}

    # -- model ------------------------------------------------------------
    def init_model(self, key=None):
        return logreg.init_params(self.d, key)

    def zero_update(self):
        return {"w": jnp.zeros((self.d,), jnp.float32),
                "b": jnp.zeros((), jnp.float32)}

    # -- per-chunk jitted runner -------------------------------------------
    def _chunk_fn(self, n: int):
        """Jitted n-iteration SGD chunk taking a (n,)-index array."""
        if n in self._chunk_fns:
            return self._chunk_fns[n]
        X, y, l2 = self.X, self.y, self.l2
        clip = self.dp_clip

        def run(w, U, eta, idx):
            def step2(carry, ix):
                w, U = carry
                g = jax.grad(logreg.per_example_loss)(w, X[ix], y[ix], l2)
                if clip > 0.0:
                    g = clip_tree(g, clip)
                U = jax.tree_util.tree_map(jnp.add, U, g)
                w = jax.tree_util.tree_map(lambda p, gg: p - eta * gg, w, g)
                return (w, U), None

            (w, U), _ = jax.lax.scan(step2, (w, U), idx)
            return w, U

        fn = jax.jit(run)
        self._chunk_fns[n] = fn
        return fn

    @staticmethod
    def _chunks(n: int):
        """Decompose n into descending power-of-two chunks (bounded jits)."""
        out, p = [], 1 << 14
        while n > 0 and p > 0:
            while p <= n:
                out.append(p)
                n -= p
            p >>= 1
        return out

    # -- Task interface ----------------------------------------------------
    def iteration_key_base(self, client_id: int, round_idx):
        """(client, round)-addressed key base for deterministic sampling."""
        base = jax.random.PRNGKey(self.sample_seed)
        return jax.random.fold_in(jax.random.fold_in(base, client_id),
                                  round_idx)

    def sample_indices(self, base, h, n: int):
        """(client, round)-keyed indices for iterations h .. h+n-1: first
        word of ``fold_in(base, h+j)`` mod n_data (single threefry)."""
        keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
            h + jnp.arange(n))
        return (keys[:, 0] % jnp.uint32(self.X.shape[0])).astype(jnp.int32)

    def run_iterations(self, w, U, *, round_idx, client_id, start_h,
                       n_iters, eta, rng):
        n_data = self.X.shape[0]
        h = int(start_h)
        for j, c in enumerate(self._chunks(int(n_iters))):
            if self.sample_seed is not None:
                base = self.iteration_key_base(client_id, round_idx)
                idx = self.sample_indices(base, h, c)
            else:
                rng, sub = jax.random.split(rng)
                keys = jax.random.split(sub, c)
                idx = jax.vmap(
                    lambda r: jax.random.randint(r, (), 0, n_data))(keys)
            w, U = self._chunk_fn(c)(w, U, jnp.float32(eta), idx)
            h += c
        return w, U

    def add_round_noise(self, w, U, *, eta, rng):
        if self.dp_sigma <= 0.0:
            return w, U
        keys = jax.random.split(rng, len(jax.tree_util.tree_leaves(U)))
        flat, treedef = jax.tree_util.tree_flatten(U)
        noise = [self.dp_clip * self.dp_sigma
                 * jax.random.normal(k, l.shape, jnp.float32)
                 for k, l in zip(keys, flat)]
        noise = jax.tree_util.tree_unflatten(treedef, noise)
        U = jax.tree_util.tree_map(jnp.add, U, noise)
        w = jax.tree_util.tree_map(lambda p, n: p + eta * n, w, noise)
        # note sign: Algorithm 1 line 24 writes ŵ = ŵ + η̄·n with U = U + n;
        # the server applies v − η̄ U, so the client pre-adds η̄·n so that a
        # later replacement ŵ = v̂ − η̄ U stays consistent.
        return w, U

    def metrics(self, w) -> Dict[str, float]:
        return {
            "loss": float(logreg.batch_loss(w, self.X, self.y, self.l2)),
            "accuracy": float(logreg.accuracy(w, self.X, self.y)),
        }


class BatchModelTask:
    """LLM-scale task: one local iteration = one minibatch-SGD step.

    ``frozen``: a base model the clients share and do not train; the
    template then holds the trained parameters alone (LoRA adapters over
    that base), and only they are client state.  The base enters every
    compiled step as an operand, never as a constant.
    """

    def __init__(self, cfg, params_template, data_fn, *, dp_clip: float = 0.0,
                 dp_sigma: float = 0.0, remat: bool = True, frozen=None):
        self.cfg = cfg
        self.data_fn = data_fn           # (client_id, round, h, rng) -> batch
        self.dp_clip = float(dp_clip)
        self.dp_sigma = float(dp_sigma)
        validate_dp_knobs(self.dp_clip, self.dp_sigma, "BatchModelTask")
        self.template = params_template
        self.frozen = frozen
        self.remat = bool(remat)

        def step(w, U, batch, eta, frozen):
            loss, g = jax.value_and_grad(self.loss)(w, batch, frozen)
            if self.dp_clip > 0.0:
                g = clip_tree(g, self.dp_clip)
            U = jax.tree_util.tree_map(jnp.add, U, g)
            w = jax.tree_util.tree_map(lambda p, gg: p - eta * gg, w, g)
            return w, U, loss

        self._step = jax.jit(step)
        self._eval_loss = jax.jit(self.loss)
        self._eval_batch = None
        self.last_loss = None

    def loss(self, params, batch, frozen=None):
        """The training loss of ``params`` (the adapters, over the
        ``frozen`` base, when the task has one)."""
        from repro.models import train_loss
        if frozen is None:
            return train_loss(self.cfg, params, batch, remat=self.remat)
        return train_loss(self.cfg, frozen, batch, remat=self.remat,
                          adapters=params)

    def init_model(self, key=None):
        """Default initial model: the params template (drivers that init
        fresh params per run may still override this attribute)."""
        return self.template

    def zero_update(self):
        return jax.tree_util.tree_map(
            lambda l: jnp.zeros(l.shape, jnp.float32), self.template)

    def run_iterations(self, w, U, *, round_idx, client_id, start_h,
                       n_iters, eta, rng):
        for h in range(int(n_iters)):
            rng, sub = jax.random.split(rng)
            batch = self.data_fn(client_id, round_idx, start_h + h, sub)
            w, U, loss = self._step(w, U, batch, jnp.float32(eta),
                                    self.frozen)
            self.last_loss = float(loss)
        return w, U

    def add_round_noise(self, w, U, *, eta, rng):
        if self.dp_sigma <= 0.0:
            return w, U
        flat, treedef = jax.tree_util.tree_flatten(U)
        keys = jax.random.split(rng, len(flat))
        noise = [self.dp_clip * self.dp_sigma
                 * jax.random.normal(k, l.shape, jnp.float32)
                 for k, l in zip(keys, flat)]
        noise = jax.tree_util.tree_unflatten(treedef, noise)
        U = jax.tree_util.tree_map(jnp.add, U, noise)
        w = jax.tree_util.tree_map(
            lambda p, n: (p + eta * n.astype(p.dtype)).astype(p.dtype),
            w, noise)
        return w, U

    def metrics(self, w) -> Dict[str, float]:
        """Eval loss of ``w`` on a fixed probe batch.

        Previously returned ``{"loss": None}`` until the first local step
        and a *stale client-side train loss* after — the engines call
        ``metrics`` on the SERVER model at eval boundaries, so histories
        carried values that never reflected the evaluated params.  The
        probe batch is the deterministic (client 0, round 0, iteration 0)
        batch, identical across engines for the same data_fn.
        """
        if self._eval_batch is None:
            self._eval_batch = self.data_fn(0, 0, 0,
                                            jax.random.PRNGKey(0))
        out = {"loss": float(self._eval_loss(w, self._eval_batch,
                                             self.frozen))}
        if self.last_loss is not None:
            out["last_train_loss"] = self.last_loss
        return out
