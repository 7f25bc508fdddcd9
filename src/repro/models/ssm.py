"""Mamba-2 SSD (state-space duality) mixer — pure-JAX chunked algorithm.

Follows the SSD formulation of [arXiv:2405.21060] §6: the sequence is split
into chunks; intra-chunk interactions are a masked matmul (dual "attention"
form), inter-chunk state is carried by a short ``lax.scan`` over chunks.
A Pallas kernel version lives in ``repro.kernels.ssd_scan`` and is verified
against :func:`ssd_chunked` (the oracle).

Decode is the classic recurrent update h' = h·exp(dtA) + dt·(B ⊗ x).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.models.common import expand_rank, fan_in_init, matmul, rms_norm


def ssm_dims(cfg):
    """(d_inner, heads H, state N, conv width of x|B|C, in_proj width):
    in_proj gives z (d_inner), x (d_inner), B and C (N each) and dt (H).
    The SSD here takes one B/C group (``ssm_n_groups`` = 1)."""
    if cfg.ssm_n_groups != 1:
        raise ValueError(f"ssm_n_groups={cfg.ssm_n_groups}: the SSD scan "
                         "takes one B/C group")
    d_inner = cfg.ssm_expand * cfg.d_model
    H = d_inner // cfg.ssm_head_dim
    N = cfg.ssm_state
    conv_dim = d_inner + 2 * N
    proj_dim = 2 * d_inner + 2 * N + H
    return d_inner, H, N, conv_dim, proj_dim


def init_ssm(cfg, key, dtype, n_layers=None):
    L = n_layers if n_layers is not None else cfg.n_layers
    d = cfg.d_model
    d_inner, H, N, conv_dim, proj_dim = ssm_dims(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "in_proj": fan_in_init(ks[0], (L, d, proj_dim), dtype),
        "conv_w": fan_in_init(ks[1], (L, conv_dim, cfg.ssm_conv_width), dtype),
        "conv_b": jnp.zeros((L, conv_dim), dtype),
        "dt_bias": jnp.zeros((L, H), dtype),
        "A_log": jnp.zeros((L, H), dtype),          # A = -exp(A_log) = -1 init
        "D": jnp.ones((L, H), dtype),
        "gate_norm": jnp.ones((L, d_inner), dtype),
        "out_proj": fan_in_init(ks[2], (L, d_inner, d), dtype),
    }
    if not cfg.ssm_conv_bias:
        del p["conv_b"]
    return p


# ---------------------------------------------------------------------------
# Chunked SSD scan (training / prefill)
# ---------------------------------------------------------------------------

def ssd_chunked(x, dt, A, B, C, chunk: int, initial_state=None,
                unroll: bool = False):
    """SSD over a full sequence.

    x: (b,s,h,p)  dt: (b,s,h)  A: (h,)  B,C: (b,s,n)  (single group).
    Returns (y (b,s,h,p), final_state (b,h,n,p)).
    """
    b, s_orig, h, p = x.shape
    n = B.shape[-1]
    Q = min(chunk, s_orig)
    pad = (-s_orig) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))   # dt=0 => no update
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    s = s_orig + pad
    nc = s // Q

    # §Perf knob: bf16 intra-chunk tensors (the (Q,Q,h) decay matrix is
    # the memory-bound term of the XLA SSD path; the Pallas kernel keeps
    # it in VMEM instead — see EXPERIMENTS.md §Perf, mamba2 iterations).
    intra_dt = (jnp.bfloat16 if os.environ.get("REPRO_SSD_BF16") == "1"
                else jnp.float32)
    xf = x.astype(jnp.float32).reshape(b, nc, Q, h, p)
    dtf = dt.astype(jnp.float32).reshape(b, nc, Q, h)
    Bc = B.astype(jnp.float32).reshape(b, nc, Q, n)
    Cc = C.astype(jnp.float32).reshape(b, nc, Q, n)
    Af = A.astype(jnp.float32)

    dA = dtf * expand_rank(Af, dtf.ndim)            # (b,nc,Q,h)
    dA_cum = jnp.cumsum(dA, axis=2)
    # intra-chunk decay matrix L[i,j] = exp(dA_cum[i] - dA_cum[j]), j <= i
    seg = dA_cum[:, :, :, None, :] - dA_cum[:, :, None, :, :]   # (b,nc,Q,Q,h)
    tril = jnp.tril(jnp.ones((Q, Q), bool))
    # mask before the exp: above the diagonal seg > 0 can overflow, and
    # an inf there makes the backward's 0 * inf a NaN
    L = jnp.exp(jnp.where(tril[None, None, :, :, None], seg,
                          -jnp.inf)).astype(intra_dt)

    CB = jnp.einsum("bcin,bcjn->bcij", Cc, Bc).astype(intra_dt)
    if os.environ.get("REPRO_SSD_TWOSTEP", "1") == "1":  # default ON (−32% mem)
        # §Perf: explicit scores + one batched (Q,Q)@(Q,P) matmul per
        # (b,c,h) — one materialization of the (Q,Q,h) tensor instead of
        # XLA's pairwise contraction order.
        scores = (CB[..., None] * L)             * dtf.astype(intra_dt)[:, :, None, :, :]
        Y_diag = jnp.einsum("bcijh,bcjhp->bcihp", scores,
                            xf.astype(intra_dt),
                            preferred_element_type=jnp.float32)
    else:
        Y_diag = jnp.einsum("bcij,bcijh,bcjh,bcjhp->bcihp",
                            CB, L, dtf.astype(intra_dt),
                            xf.astype(intra_dt),
                            preferred_element_type=jnp.float32)

    # per-chunk end state contribution
    dA_sum = dA_cum[:, :, -1]                                   # (b,nc,h)
    w = jnp.exp(dA_sum[:, :, None] - dA_cum) * dtf              # (b,nc,Q,h)
    states = jnp.einsum("bcjh,bcjn,bcjhp->bchnp", w, Bc, xf)    # (b,nc,h,n,p)

    init = (jnp.zeros((b, h, n, p), jnp.float32) if initial_state is None
            else initial_state.astype(jnp.float32))

    def step(carry, inp):
        s_c, dA_s = inp                                         # (b,h,n,p),(b,h)
        new = carry * jnp.exp(dA_s)[..., None, None] + s_c
        return new, carry                                       # emit prev

    st_t = states.transpose(1, 0, 2, 3, 4)
    da_t = dA_sum.transpose(1, 0, 2)
    if unroll:
        carry, prevs = init, []
        for ci in range(nc):
            carry, out = step(carry, (st_t[ci], da_t[ci]))
            prevs.append(out)
        final, prev = carry, jnp.stack(prevs)
        prev = prev.transpose(1, 0, 2, 3, 4)                    # (b,nc,h,n,p)
    elif os.environ.get("REPRO_SSD_ASSOC") == "1" and initial_state is None:
        # §Perf: the inter-chunk linear recurrence as an associative scan
        # (log-depth tree) — avoids per-step resharding of the
        # model-axis-sharded chunk dimension in the sequential lax.scan.
        alpha = jnp.exp(dA_sum)[..., None, None]                # (b,nc,h,1,1)

        def combine(l, r):
            al, sl = l
            ar, sr = r
            return al * ar, sr + ar * sl

        a_inc, s_inc = jax.lax.associative_scan(
            combine, (alpha, states), axis=1)
        # inclusive prefix h_c; previous state = shift right with init
        prev = jnp.concatenate(
            [jnp.broadcast_to(init[:, None], states[:, :1].shape),
             s_inc[:, :-1]], axis=1)
        final = s_inc[:, -1]
    else:
        final, prev = jax.lax.scan(step, init, (st_t, da_t))
        prev = prev.transpose(1, 0, 2, 3, 4)                    # (b,nc,h,n,p)

    Y_off = jnp.einsum("bcin,bcih,bchnp->bcihp",
                       Cc, jnp.exp(dA_cum), prev)
    y = (Y_diag + Y_off).reshape(b, s, h, p)[:, :s_orig].astype(x.dtype)
    return y, final


def causal_depthwise_conv(x, w, b=None):
    """x: (B,S,C), w: (C,W), b: (C,) or None.  Causal depthwise conv."""
    W = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp.astype(jnp.float32),
        w.T[:, None, :].astype(jnp.float32),        # (W,1,C) -> spec below
        window_strides=(1,),
        padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1],
    )
    if b is not None:
        out = out + expand_rank(b.astype(jnp.float32), out.ndim)
    return out.astype(x.dtype)


def _split_proj(cfg, zxbcdt):
    d_inner, H, N, _, _ = ssm_dims(cfg)
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner:2 * d_inner + 2 * N]
    dt = zxbcdt[..., 2 * d_inner + 2 * N:]
    return z, xBC, dt, d_inner, H, N


def apply_ssm(cfg, lp, x, *, return_state: bool = False, ssd_fn=None,
              unroll: bool = False, dense=matmul):
    """Full-sequence mamba2 mixer.  x: (B,S,d) -> (B,S,d).  ``dense(name,
    x, w)`` computes the ``in_proj`` and ``out_proj`` projections."""
    B_, S, _ = x.shape
    zxbcdt = dense("in_proj", x, lp["in_proj"])
    z, xBC, dt, d_inner, H, N = _split_proj(cfg, zxbcdt)

    xBC = jax.nn.silu(causal_depthwise_conv(xBC, lp["conv_w"],
                                            lp.get("conv_b")))
    xs = xBC[..., :d_inner]
    Bm = xBC[..., d_inner:d_inner + N]
    Cm = xBC[..., d_inner + N:]

    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + expand_rank(lp["dt_bias"].astype(jnp.float32),
                                       dt.ndim))
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))

    P = cfg.ssm_head_dim
    xh = xs.reshape(B_, S, H, P)
    chunk = int(os.environ.get("REPRO_SSD_CHUNK", cfg.ssm_chunk))
    if ssd_fn is not None:
        y, final = ssd_fn(xh, dt, A, Bm, Cm, chunk)
    else:
        y, final = ssd_chunked(xh, dt, A, Bm, Cm, chunk,
                               unroll=unroll)
    y = y + lp["D"].astype(y.dtype)[None, None, :, None] * xh
    y = y.reshape(B_, S, d_inner)

    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 lp["gate_norm"], cfg.norm_eps)
    out = dense("out_proj", y, lp["out_proj"])
    if return_state:
        # conv state: last (W-1) xBC inputs (pre-activation path needs raw
        # conv input; we store the raw projection tail)
        raw_xBC = zxbcdt[..., d_inner:2 * d_inner + 2 * N]
        W = cfg.ssm_conv_width
        conv_state = raw_xBC[:, -(W - 1):, :]
        return out, final, conv_state
    return out


def init_ssm_state(cfg, batch: int, n_layers=None):
    L = n_layers if n_layers is not None else cfg.n_layers
    d_inner, H, N, conv_dim, _ = ssm_dims(cfg)
    P = cfg.ssm_head_dim
    return {
        "h": jnp.zeros((L, batch, H, N, P), jnp.float32),
        "conv": jnp.zeros((L, batch, cfg.ssm_conv_width - 1, conv_dim),
                          jnp.float32),
    }


def decode_ssm(cfg, lp, x, h_state, conv_state):
    """Single-token recurrent step.

    x: (B,1,d); h_state: (B,H,N,P); conv_state: (B,W-1,conv_dim).
    Returns (out (B,1,d), new_h, new_conv).
    """
    B_ = x.shape[0]
    zxbcdt = jnp.einsum("bsd,dk->bsk", x, lp["in_proj"])[:, 0]  # (B,k)
    z, xBC, dt, d_inner, H, N = _split_proj(cfg, zxbcdt[:, None, :])
    z, xBC, dt = z[:, 0], xBC[:, 0], dt[:, 0]

    # conv ring: window = [conv_state, xBC]
    win = jnp.concatenate([conv_state.astype(xBC.dtype), xBC[:, None, :]],
                          axis=1)                               # (B,W,conv)
    conv_out = jnp.einsum("bwc,cw->bc", win.astype(jnp.float32),
                          lp["conv_w"].astype(jnp.float32)) \
        + expand_rank(lp["conv_b"].astype(jnp.float32), 2)
    xBC_act = jax.nn.silu(conv_out)
    new_conv = win[:, 1:, :].astype(jnp.float32)

    xs = xBC_act[..., :d_inner]
    Bm = xBC_act[..., d_inner:d_inner + N]
    Cm = xBC_act[..., d_inner + N:]

    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + expand_rank(lp["dt_bias"].astype(jnp.float32),
                                       dt.ndim))                # (B,H)
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))               # (H,)
    P = cfg.ssm_head_dim
    xh = xs.reshape(B_, H, P).astype(jnp.float32)

    decay = jnp.exp(dt * expand_rank(A, dt.ndim))               # (B,H)
    new_h = h_state * decay[..., None, None] \
        + jnp.einsum("bh,bn,bhp->bhnp", dt, Bm, xh)
    y = jnp.einsum("bn,bhnp->bhp", Cm, new_h) \
        + lp["D"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(B_, d_inner)

    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)),
                 lp["gate_norm"], cfg.norm_eps)
    out = jnp.einsum("bk,kd->bd", y.astype(x.dtype), lp["out_proj"])
    return out[:, None, :], new_h, new_conv
