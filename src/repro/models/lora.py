"""Rank-r LoRA adapters (arXiv 2106.09685) on named projections.

A projection ``x @ W`` with an adapter ``(A [in, r], B [r, out])``
computes ``x @ W + (alpha / r) * (x @ A) @ B``; the adapter is never
merged into ``W``, so one frozen base serves every client's adapters.
A is drawn at random and B is zero, so a fresh adapter leaves the base
model's function unchanged.

The adapters of a ``layer_types`` model (``repro.models.transformer``)
mirror its runs of same-kind layers: ``LoRA.layers[r]`` holds run r's
adapters, each leaf stacked over the run's layers like the base's own,
keyed by module (``ssm``, ``attn``, ``mlp``) and weight (``in_proj``,
``wq``, ...), each an ``{"a", "b"}`` pair.  ``LoRA`` is a pytree whose
leaves are the adapters alone; rank and alpha ride in its structure, so
a ``PyTreeFlattener`` of it ravels the adapters and nothing else.

Every projection computes in the weight's dtype with float32
accumulation: a bfloat16 base takes bfloat16 inputs (the adapters are
cast to it as well), a float32 base computes in float32.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

#: the projections adapted by default: every projection of the mixers
#: and of the MLP
TARGETS: Dict[str, Tuple[str, ...]] = {
    "ssm": ("in_proj", "out_proj"),
    "attn": ("wq", "wk", "wv", "wo"),
    "mlp": ("wi", "wd"),
}


@jax.tree_util.register_pytree_node_class
class LoRA:
    """Adapters of every run of layers, with their rank and alpha."""

    def __init__(self, layers, rank: int, alpha: float):
        self.layers = layers
        self.rank = int(rank)
        self.alpha = float(alpha)

    @property
    def scale(self) -> float:
        return self.alpha / self.rank

    def tree_flatten(self):
        return (self.layers,), (self.rank, self.alpha)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def init_lora(base_runs: Sequence[Dict], key, *, rank: int = 16,
              alpha: float = 32.0,
              targets: Dict[str, Tuple[str, ...]] = None) -> LoRA:
    """Adapters for every ``targets`` weight present in ``base_runs``
    (the ``runs`` of a ``layer_types`` model's params): A [n, in, r]
    uniform in +-1/sqrt(in) (PEFT's Kaiming-uniform with a = sqrt(5)),
    B [n, r, out] zero, float32."""
    targets = TARGETS if targets is None else targets
    runs = []
    for run in base_runs:
        ad = {}
        for mod, names in targets.items():
            for name in names:
                if mod not in run or name not in run[mod]:
                    continue
                n, d_in, d_out = run[mod][name].shape
                key, sub = jax.random.split(key)
                bound = 1.0 / math.sqrt(d_in)
                ad.setdefault(mod, {})[name] = {
                    "a": jax.random.uniform(sub, (n, d_in, rank),
                                            jnp.float32, -bound, bound),
                    "b": jnp.zeros((n, rank, d_out), jnp.float32)}
        runs.append(ad)
    return LoRA(tuple(runs), rank, alpha)


def linear(x, w, adapter: Optional[Dict] = None, scale: float = 1.0):
    """``x @ w`` (+ ``scale * (x @ A) @ B``), in ``w``'s dtype with
    float32 accumulation; x: (..., in), w: (in, out) -> (..., out) f32."""
    dt = w.dtype
    xd = x.astype(dt)
    y = jnp.einsum("...i,io->...o", xd, w, preferred_element_type=jnp.float32)
    if adapter is None:
        return y
    with jax.named_scope("model.lora"):
        t = jnp.einsum("...i,ir->...r", xd, adapter["a"].astype(dt),
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("...r,ro->...o", t.astype(dt),
                       adapter["b"].astype(dt),
                       preferred_element_type=jnp.float32)
    return y + scale * u


def projection(adapters: Optional[Dict], scale: float):
    """A ``dense(name, x, w)`` for one module of one layer: ``linear``
    with the adapter of weight ``name``, where ``adapters`` has one."""
    def dense(name, x, w):
        ad = None if adapters is None else adapters.get(name)
        return linear(x, w, ad, scale)
    return dense
