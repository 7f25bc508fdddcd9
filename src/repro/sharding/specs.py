"""Per-architecture PartitionSpecs (GSPMD rules, divisibility-checked).

Strategy (baseline, recorded in EXPERIMENTS.md §Roofline):
  * ``model`` axis: tensor-parallel — shards attention head projections,
    MLP hidden, expert hidden, vocab (where divisible).
  * ``data`` axis: FSDP — shards the *other* matrix dimension of each
    large parameter (d_model side), plus the batch dimension of
    activations.
  * ``pod`` axis (multi-pod): FL clients — parameters are replicated
    across pods (each pod is one client cohort holding a full model
    replica, sharded within the pod); the FL server reduce is the only
    cross-pod collective, matching the paper's communication model.

Every rule degrades gracefully: an axis is applied to a tensor dimension
only when the dimension is divisible by the axis size, so every assigned
architecture lowers on both production meshes without bespoke cases.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# §Perf knob: disable FSDP (data-axis) sharding of parameters — for models
# whose model-parallel shard already fits HBM this removes the per-layer
# weight all-gather (see EXPERIMENTS.md §Perf).
NO_FSDP = os.environ.get("REPRO_NO_FSDP", "0") == "1"


def _axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def _fit(mesh: Mesh, dim: int, axis: str):
    """Return axis name if dim divisible by its size, else None."""
    if axis == "data" and NO_FSDP:
        return None
    return axis if (axis in mesh.axis_names and dim % _axis_size(mesh, axis)
                    == 0 and _axis_size(mesh, axis) > 1) else None


def _spec_for(mesh: Mesh, path: str, shape: Tuple[int, ...]) -> P:
    """Rule table keyed on parameter leaf name."""
    name = path.split("/")[-1]

    def fit(i, axis):
        return _fit(mesh, shape[i], axis)

    nd = len(shape)
    if name in ("embed", "unembed"):                       # (V, d)
        v_ax = fit(0, "model")
        d_ax = fit(1, "data")
        if v_ax is None:                                   # odd vocab sizes
            return P(None, fit(1, "model"))
        return P(v_ax, d_ax)
    if name in ("wq", "wk", "wv"):                         # (L, d, out)
        return P(None, fit(1, "data"), fit(2, "model"))
    if name == "wo":                                       # (L, out, d)
        return P(None, fit(1, "model"), fit(2, "data"))
    if name in ("wg", "wu"):
        if nd == 4:                                        # moe (L,E,d,ff)
            return P(None, None, fit(2, "data"), fit(3, "model"))
        return P(None, fit(1, "data"), fit(2, "model"))    # (L, d, ff)
    if name == "wd":
        if nd == 4:                                        # moe (L,E,ff,d)
            return P(None, None, fit(2, "model"), fit(3, "data"))
        return P(None, fit(1, "model"), fit(2, "data"))    # (L, ff, d)
    if name in ("shared_wg", "shared_wu"):                 # (L, d, sf)
        return P(None, fit(1, "data"), fit(2, "model"))
    if name == "shared_wd":                                # (L, sf, d)
        return P(None, fit(1, "model"), fit(2, "data"))
    if name == "router":                                   # (L, d, E)
        return P(None, fit(1, "data"), None)
    if name == "in_proj":                                  # (L, d, proj)
        return P(None, fit(1, "data"), fit(2, "model"))
    if name == "out_proj":                                 # (L, d_in, d)
        return P(None, fit(1, "model"), fit(2, "data"))
    if name == "conv_w":                                   # (L, conv_dim, W)
        return P(None, fit(1, "model"), None)
    if name in ("conv_b", "gate_norm"):                    # (L, conv_dim)
        return P(None, fit(1, "model"))
    if name in ("bq", "bk", "bv"):                         # (L, out)
        return P(None, fit(1, "model"))
    if name in ("bu",):                                    # (L, ff)
        return P(None, fit(1, "model"))
    if name in ("bd",):                                    # (L, d)
        return P(None, fit(1, "data"))
    # norms, dt_bias, A_log, D, scalars: replicate
    return P(*([None] * nd))


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_pspecs(mesh: Mesh, params_shape: Any) -> Any:
    """Map a params shape-pytree to PartitionSpecs."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _spec_for(mesh, _path_str(path), leaf.shape),
        params_shape)


def param_shardings(mesh: Mesh, params_shape: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_pspecs(mesh, params_shape))


# ---------------------------------------------------------------------------
# Cohort engine: client-axis sharding for [C, D] population state
# ---------------------------------------------------------------------------

def cohort_mesh(devices=None) -> Mesh:
    """1-D mesh whose axis ``clients`` shards the population axis of the
    cohort engines' stacked state.

    ``devices`` defaults to the one device set with ``jax.default_device``
    (so ``with jax.default_device(jax.devices()[0]):`` keeps an engine
    built inside on one chip), else every local device.
    """
    if devices is None:
        one = jax.config.jax_default_device
        if isinstance(one, str):                 # a platform name
            one = jax.devices(one)[0]
        devices = [one] if one is not None else jax.devices()
    return Mesh(np.asarray(list(devices)), ("clients",))


def cohort_pspecs(mesh: Mesh, n_clients: int) -> Dict[str, P]:
    """Field -> PartitionSpec for ``DeviceCohortState``-shaped pytrees.

    Client-axis fields ([C, ...] or [..., C]) shard over ``clients`` when
    C is divisible by the axis size; the server model, the message rings'
    payloads ([L, D] / [B, D]) and all scalars replicate — they are what
    the batched server reduce touches, i.e. the FL analogue of the
    cross-pod reduce in the LLM mapping.
    """
    c_ax = _fit(mesh, n_clients, "clients")
    return {
        "w": P(c_ax, None), "U": P(c_ax, None), "v": P(None),
        "i": P(c_ax), "h": P(c_ax), "k": P(c_ax), "credit": P(c_ax),
        "server_k": P(), "tick": P(),
        "upd_vec": P(None, None), "upd_cnt": P(None, None),
        "h_counts": P(None),
        "bc_v": P(None, None), "bc_k": P(None), "bc_at": P(None, c_ax),
        "ovf_vec": P(None, None), "ovf_at": P(None),
        "ovf_cnt": P(None, None), "err": P(),
        "messages": P(), "broadcasts": P(),
        # telemetry counters: per-client census shards with the client
        # axis; the small histogram / ring-count arrays and scalar
        # high-water marks replicate like the message rings they mirror
        "part": P(c_ax), "bytes_up": P(c_ax),
        "stale_hist": P(None), "upd_ks": P(None, None),
        "ovf_ks": P(None, None), "ovf_hwm": P(), "far_msgs": P(),
        # aggregation-strategy buffers (repro.core.strategies): server-
        # side ring payloads and the FedBuff accumulator replicate like
        # the message rings they extend ([1, ...] dummies under the
        # default paper strategy)
        "upd_kvec": P(None, None, None), "ovf_kvec": P(None, None, None),
        "buf_vec": P(None), "buf_cnt": P(),
        # op-census vector (repro.telemetry.costs): scalar-ish counter
        # block, replicates like the other telemetry scalars
        "ops": P(None),
        # fused-loop iteration census ([loop_iters, block_iters]):
        # scalar-ish, replicates like ops
        "iters": P(None),
    }


def cohort_shardings(mesh: Mesh, n_clients: int) -> Dict[str, Any]:
    return {f: NamedSharding(mesh, s)
            for f, s in cohort_pspecs(mesh, n_clients).items()}


# ---------------------------------------------------------------------------
# Activations / batches / caches
# ---------------------------------------------------------------------------

def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    return tuple(axes)


def batch_spec(mesh: Mesh, batch_size: int, extra_dims: int = 1) -> P:
    """Shard the batch dim over (pod, data) when divisible."""
    axes = [a for a in batch_axes(mesh)
            if batch_size % _axis_size(mesh, a) == 0]
    # try combined first
    combined = batch_axes(mesh)
    total = int(np.prod([_axis_size(mesh, a) for a in combined])) \
        if combined else 1
    if combined and batch_size % total == 0:
        lead = combined if len(combined) > 1 else combined[0]
    elif axes:
        lead = axes[0]
    else:
        lead = None
    return P(lead, *([None] * extra_dims))


def client_batch_spec(mesh: Mesh, per_client_batch: int,
                      extra_dims: int = 1) -> P:
    """(C, B, ...) batches: client axis over pod, batch over data."""
    c_ax = "pod" if "pod" in mesh.axis_names else None
    b_ax = _fit(mesh, per_client_batch, "data")
    return P(c_ax, b_ax, *([None] * extra_dims))


def cache_pspecs(mesh: Mesh, cache_shape: Any) -> Any:
    """Decode-cache sharding: batch over (pod,data) if divisible, else
    shard heads / state over model; fall back to replication."""
    def spec(path, leaf):
        shape = leaf.shape
        name = _path_str(path).split("/")[-1]
        if name in ("k", "v", "cross_k", "cross_v"):
            # (L, B, S_cache, KV, hd)
            b = _fit_combined(mesh, shape[1])
            kv = _fit(mesh, shape[3], "model")
            s = None
            if kv is None:
                s = _fit(mesh, shape[2], "model")
            return P(None, b, s, kv, None)
        if name in ("k_scale", "v_scale"):
            # (L, B, S_cache, KV) — int8-KV scales, mirror the kv layout
            b = _fit_combined(mesh, shape[1])
            kv = _fit(mesh, shape[3], "model")
            s = None
            if kv is None:
                s = _fit(mesh, shape[2], "model")
            return P(None, b, s, kv)
        if name == "h":          # ssm state (L, B, H, N, P)
            b = _fit_combined(mesh, shape[1])
            h_ax = _fit(mesh, shape[2], "model")
            return P(None, b, h_ax, None, None)
        if name == "conv":       # (L, B, W-1, conv_dim)
            b = _fit_combined(mesh, shape[1])
            return P(None, b, None, _fit(mesh, shape[3], "model"))
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(spec, cache_shape)


def _fit_combined(mesh: Mesh, dim: int):
    combined = batch_axes(mesh)
    total = int(np.prod([_axis_size(mesh, a) for a in combined])) \
        if combined else 1
    if combined and dim % total == 0 and total > 1:
        return combined if len(combined) > 1 else combined[0]
    for a in combined:
        if dim % _axis_size(mesh, a) == 0 and _axis_size(mesh, a) > 1:
            return a
    return None
