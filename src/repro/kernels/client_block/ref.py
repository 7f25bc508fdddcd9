"""Pure-jnp reference for the fused local-SGD client block.

This is the computation ``CohortLogRegTask`` ran as plain XLA before the
kernel existed: a ``vmap`` over clients of a ``lax.scan`` over local
steps, on the model split into ``{"w": w[:d], "b": w[d]}``.  It is the
CPU dispatch target, so the golden fixtures and host-vs-device parity
rest on it: keep its expressions, and their order, as they are.
"""
import jax
import jax.numpy as jnp

from repro.core.tasks import clip_tree
from repro.models import logreg


def client_block_ref(w, U, idx, n, eta, X, y, *, l2: float, clip: float):
    """Advance every client by ``idx.shape[1]`` masked local SGD steps.

    w, U  [C, D]      client models and round updates, D = d + 1 (bias)
    idx   [C, block]  sample row of each client's step j
    n     [C]         steps to take; steps j >= n[c] are masked
    eta   [C]         round step sizes
    X, y  [N, d], [N] the data set
    """
    d = X.shape[1]
    block = idx.shape[1]

    def per_client(w_c, U_c, idx_c, n_c, eta_c):
        params = {"w": w_c[:d], "b": w_c[d]}
        upd = {"w": U_c[:d], "b": U_c[d]}

        def body(carry, inp):
            p, u = carry
            i, j = inp
            g = jax.grad(logreg.per_example_loss)(p, X[i], y[i], l2)
            if clip > 0.0:
                g = clip_tree(g, clip)
            act = (j < n_c).astype(jnp.float32)
            g = jax.tree_util.tree_map(lambda l: act * l, g)
            u = jax.tree_util.tree_map(jnp.add, u, g)
            p = jax.tree_util.tree_map(lambda a, gg: a - eta_c * gg, p, g)
            return (p, u), None

        (params, upd), _ = jax.lax.scan(body, (params, upd),
                                        (idx_c, jnp.arange(block)))
        w_out = jnp.concatenate([params["w"], params["b"][None]])
        u_out = jnp.concatenate([upd["w"], upd["b"][None]])
        return w_out, u_out

    return jax.vmap(per_client)(w, U, idx, n, eta)
