"""Plain reference of the protocol a job runs.  Imports nothing of the
program under test and takes nothing it made: it re-derives the local
samples, the fleet's speeds and windows and the DP noise from the seed,
by the derivations the program documents, and computes every round in
float32 with elementwise products and sums (no matrix-unit passes, so
no precision lower than float32 on any backend).

The protocol (the paper's Algorithm 1 with the wait gate d, in ticks):
each tick the server applies the updates that arrive, and broadcasts
its model each time every client's update of the current round is in.
A client that holds broadcast k takes its freshest one (w = v_k - eta U),
computes while its round i < k + d and it is available, earning
``block * speed`` local steps a tick, and when its s_i steps are done
sends eta * U (plus eta * noise under DP, which it also adds to w) and
clears U.  A local step samples row ``fold_in(fold_in(fold_in(key(seed),
client), round), step)[0] mod N`` and takes a logistic-regression
gradient step (per-example clipped under DP).

``fedsgd`` is the closed form of that protocol for one local step per
round, d = 1, one-tick latency, an always-on fleet of equal speed and no
DP: every client computes its round-k gradient at the server's v_k, so
v_{k+1} = v_k - eta_k * Sum_c grad(v_k; sample(c, k)).  ``ticks`` runs
the protocol tick by tick for every other mix.

``low=True`` is the control: the same computation with the client
block's data rows and model rows rounded to bfloat16 (float32
accumulation), the step a later change to the client block might take.
"""
from __future__ import annotations

import functools
from collections import defaultdict
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .spec import load_module

FRAC_BITS = 16              # fixed-point fractional bits of step credit
NOISE_XOR = 0x5EED          # DP noise: key(seed ^ NOISE_XOR), fold_in(tick)
MAX_TICKS = 1_000_000       # a protocol that stalls raises here


def _client_keys(sample_seed: int, C: int):
    base = jax.random.PRNGKey(sample_seed)
    return jax.vmap(jax.random.fold_in, (None, 0))(base, jnp.arange(C))


def _sample(ckeys, i, step, n_data):
    """[C] sample rows of clients at rounds i [C], steps step [C]."""
    rk = jax.vmap(jax.random.fold_in)(ckeys, i)
    sk = jax.vmap(jax.random.fold_in)(rk, step)
    return (sk[:, 0] % jnp.uint32(n_data)).astype(jnp.int32)


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _grad_rows(x, yy, w, b, l2, clip, low):
    """Per-row logistic-regression gradients (g_w [n, d], g_b [n]) of
    rows x [n, d] at models w [n, d]; exact float32 products and sums,
    or bfloat16 inputs with float32 products and sums (``low``)."""
    xz, wz = (_bf16(x), _bf16(w)) if low else (x, w)
    z = jnp.sum(xz * wz, axis=-1) + b
    r = jax.nn.sigmoid(z) - yy
    gw = r[:, None] * xz + l2 * w
    gb = r
    if clip > 0.0:
        norm = jnp.sqrt(jnp.sum(gw * gw, axis=1) + gb * gb)
        scale = 1.0 / jnp.maximum(1.0, norm / clip)
        gw, gb = gw * scale[:, None], gb * scale
    return gw, gb


# -- closed form: FedSGD ------------------------------------------------

@functools.partial(jax.jit, static_argnames=("C", "row_block", "low",
                                             "l2"))
def _fedsgd_round(v, X, y, ckeys, k, eta, *, C, row_block, low, l2):
    n_data, d = X.shape
    nb = C // row_block

    def block(carry, b):
        gw_sum, gb_sum = carry
        keys = jax.lax.dynamic_slice_in_dim(ckeys, b * row_block,
                                            row_block)
        idx = _sample(keys, jnp.full((row_block,), k, jnp.int32),
                      jnp.zeros((row_block,), jnp.int32), n_data)
        x, yy = X[idx], y[idx]
        w = jnp.broadcast_to(v[:d], (row_block, d))
        gw, gb = _grad_rows(x, yy, w, v[d], l2, 0.0, low)
        return (gw_sum + jnp.sum(gw, axis=0), gb_sum + jnp.sum(gb)), None

    (gw, gb), _ = jax.lax.scan(
        block, (jnp.zeros((d,), jnp.float32), jnp.float32(0.0)),
        jnp.arange(nb))
    return v - eta * jnp.concatenate([gw, gb[None]])


def fedsgd(X, y, v0, etas, *, C: int, rounds: int, sample_seed: int,
           l2: float, low: bool = False) -> Dict:
    row_block = min(C, 16384)
    if C % row_block:
        raise ValueError(f"C={C} is not a multiple of {row_block}")
    ckeys = _client_keys(sample_seed, C)
    v = v0
    etas = np.asarray(etas, np.float32)
    for k in range(rounds):
        v = _fedsgd_round(v, X, y, ckeys, jnp.int32(k), etas[k], C=C,
                          row_block=row_block, low=low, l2=float(l2))
    return {"v": np.asarray(v),
            "census": {"messages": C * rounds, "broadcasts": rounds,
                       "ticks": 2 * rounds}}


# -- tick by tick ---------------------------------------------------------

def fleet(spec: Dict, C: int, seed: int, block: int):
    """Per-tick step credit [C] (fixed point) and the availability
    function of the fleet the traffic file states: its ``speeds`` and
    ``availability`` kinds, each a file of ``bench/fleets/``."""
    sp, av = spec["speeds"], spec["availability"]
    s = load_module("fleets/speeds", sp["kind"]).speeds(sp, C, seed)
    s = np.maximum(s, 1e-3)
    s = s / s.max()
    accrual = (np.maximum(1, np.round(s * (1 << FRAC_BITS)))
               .astype(np.int64) * block)
    dt = block / s.max()
    avail = load_module("fleets/availability", av["kind"]).availability(
        av, C, seed, dt)
    return accrual, avail


@functools.partial(jax.jit, static_argnames=("l2", "clip", "low"))
def _block(w, U, X, y, ckeys, i, h, n, eta, nmax, *, l2, clip, low):
    n_data, d = X.shape

    def step(j, wu):
        w, U = wu
        idx = _sample(ckeys, i, h + j, n_data)
        gw, gb = _grad_rows(X[idx], y[idx], w[:, :d], w[:, d], l2, clip,
                            low)
        g = jnp.concatenate([gw, gb[:, None]], axis=1)
        g = g * (j < n).astype(jnp.float32)[:, None]
        return w - eta[:, None] * g, U + g

    return jax.lax.fori_loop(0, nmax, step, (w, U))


@functools.partial(jax.jit, static_argnames=("noise_scale",))
def _complete(w, U, done, eta, noise_key, *, noise_scale):
    donef = done.astype(jnp.float32)
    if noise_scale > 0.0:
        noise = jax.random.normal(noise_key, U.shape, jnp.float32)
        sent = U + (noise_scale * donef)[:, None] * noise
        w = jnp.where(done[:, None], w + eta[:, None] * (sent - U), w)
    else:
        sent = U
    vec = jnp.sum(sent * (eta * donef)[:, None], axis=0,
                  dtype=jnp.float32)
    return w, jnp.where(done[:, None], 0.0, U), vec


def ticks(X, y, v0, etas, sizes, *, C: int, rounds: int, d_gate: int,
          block: int, fleet_spec: Dict, seed: int, sample_seed: int,
          l2: float, clip: float, noise_scale: float,
          low: bool = False) -> Dict:
    accrual, avail = fleet(fleet_spec, C, seed, block)
    lat = int(fleet_spec.get("latency_ticks", 1))
    ckeys = _client_keys(sample_seed, C)
    noise_base = jax.random.PRNGKey(seed ^ NOISE_XOR)
    etas = np.asarray(etas, np.float32)
    sizes = np.asarray(sizes, np.int64)
    D = v0.shape[0]
    v = v0
    w = jnp.tile(v0[None, :], (C, 1))
    U = jnp.zeros((C, D), jnp.float32)
    i = np.zeros(C, np.int64)
    h = np.zeros(C, np.int64)
    k = np.zeros(C, np.int64)
    credit = np.zeros(C, np.int64)
    bucket: Dict[int, list] = {}            # arrival tick -> [vec, counts]
    hcount: Dict[int, int] = defaultdict(int)
    bcs: List = []                          # (k, v_k, arrival tick)
    server_k = messages = t = 0
    while server_k < rounds:
        t += 1
        if t > MAX_TICKS:
            raise RuntimeError(f"reference stalled at tick {t}")
        due = bucket.pop(t, None)
        if due is not None:
            v = v - due[0]
            for r, c in due[1].items():
                hcount[r] += c
        while hcount[server_k] >= C:
            del hcount[server_k]
            server_k += 1
            bcs.append((server_k, v, t + lat))
        eta = etas[np.minimum(i, len(etas) - 1)]
        ready = [b for b in bcs if b[2] <= t]
        if ready:
            kb, vb, _ = max(ready, key=lambda b: b[0])
            take = kb > k
            if take.any():
                w = jnp.where(jnp.asarray(take)[:, None],
                              vb[None, :] - jnp.asarray(eta)[:, None] * U,
                              w)
                k = np.where(take, kb, k)
        active = i < k + d_gate
        if avail is not None:
            active &= avail(t)
        credit = credit + np.where(active, accrual, 0)
        s_i = sizes[np.minimum(i, len(sizes) - 1)]
        n = np.maximum(np.where(active, np.minimum(s_i - h,
                                                   credit >> FRAC_BITS),
                                0), 0)
        credit = credit - (n << FRAC_BITS)
        if n.max() > 0:
            w, U = _block(w, U, X, y, ckeys, jnp.asarray(i, jnp.int32),
                          jnp.asarray(h, jnp.int32),
                          jnp.asarray(n, jnp.int32), jnp.asarray(eta),
                          jnp.int32(n.max()), l2=float(l2),
                          clip=float(clip), low=low)
        h = h + n
        done = active & (h >= s_i)
        if done.any():
            w, U, vec = _complete(w, U, jnp.asarray(done), jnp.asarray(eta),
                                  jax.random.fold_in(noise_base, t),
                                  noise_scale=float(noise_scale))
            slot = bucket.setdefault(t + lat, [jnp.zeros_like(v),
                                               defaultdict(int)])
            slot[0] = slot[0] + vec
            for r, c in zip(*np.unique(i[done], return_counts=True)):
                slot[1][int(r)] += int(c)
            messages += int(done.sum())
            i = np.where(done, i + 1, i)
            h = np.where(done, 0, h)
            credit = np.where(done, np.minimum(credit, block << FRAC_BITS),
                              credit)
    return {"v": np.asarray(v),
            "census": {"messages": messages, "broadcasts": server_k,
                       "ticks": t}}


def gap(v: np.ndarray, v_ref: np.ndarray, v0: np.ndarray) -> float:
    """Widest gap to the reference, as a share of the widest change the
    reference made to the model."""
    change = float(np.max(np.abs(v_ref - v0)))
    return float(np.max(np.abs(v - v_ref))) / max(change, 1e-30)
