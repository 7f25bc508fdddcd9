"""Plain reference of a ``"reference": "fedavg"`` mix over the
granite_h_lora model: granite-4.0-h-micro's layers under rank-r LoRA,
written from the published description in plain ``jax.numpy`` float32
(every matmul at ``jax.default_matmul_precision("highest")``), and the
protocol's closed form.  Imports nothing of the program and takes
nothing it made: the base and the adapters come from the seed
(``bench/models/granite_h_lora.py``, which also gives the flat order),
the minibatches are re-derived here.

The model.  x = embed[tokens] x embedding_multiplier; each layer of
``layer_types[:layers]`` adds residual_multiplier x mixer(rms(x)) and
then residual_multiplier x mlp(rms(x)); logits = rms(x) @ embed^T /
logits_scaling; the loss is the mean next-token cross entropy.  A
projection is x W + (alpha / r) (x A) B.  The Mamba-2 mixer: in_proj
-> z | x | B | C | dt; a causal depthwise conv (width K, bias) and
SiLU over x | B | C; dt = softplus(dt + dt_bias), A = -exp(A_log); the
SSM y_t = sum_{s <= t} (C_t . B_s) exp(sum_{s < u <= t} dt_u A) dt_s
x_s + D x_t, written as its full (S x S) matrix per head, not chunked;
y = rms(y * silu(z)) * gate_norm; out_proj.  Attention: grouped-query,
no position embedding, scores q . k x attention_multiplier, causal.
The MLP: silu(gate) * up from one fused projection, then down.

The minibatches: key = fold_in(fold_in(fold_in(PRNGKey(seed), client),
round), step); split five ways (a, b, start, mask, noise); a = 2
randint[1, 8) + 1, b and start randint[0, V) per row, token t =
(start + a t + b (t // 7)) mod V, replaced by randint[0, V) where
uniform < 0.05.

The protocol's closed form, for constant s, d = 1, one-tick latency,
no DP and an equal, always-on fleet under the paper's aggregation:
every client takes s LoRA-SGD steps from v_k (w <- w - eta_k g), its
update U_c the sum of its gradients, and v_{k+1} = v_k - eta_k Sum_c
U_c; the census is C R messages, R broadcasts and R (ceil(s / block) +
1) ticks.  Any other mix is refused.

``low=True`` is the control, one precision step below the
configuration's: the matmul inputs in bfloat16 with float32 sums (as
the configuration states), and besides the residual stream, the SSM's
output, the adapters (client and server) and the updates rounded to
bfloat16, where the configuration keeps them in float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from yardstick.data import seed32
from yardstick.spec import load_module

BF = jnp.bfloat16


def _round16(x, low):
    return x.astype(BF).astype(jnp.float32) if low else x


def _mm(x, w, low):
    """x @ w in float32, or with bfloat16 inputs and float32 sums."""
    if low:
        return jnp.einsum("...i,io->...o", x.astype(BF), w.astype(BF),
                          preferred_element_type=jnp.float32)
    return jnp.einsum("...i,io->...o", x, w.astype(jnp.float32))


def _proj(x, w, ad, scale, low):
    y = _mm(x, w, low)
    if ad is None:
        return y
    return y + scale * _mm(_mm(x, ad["a"], low), ad["b"], low)


def _rms(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * w.astype(jnp.float32)[None, :])


def _mamba(x, p, ad, scale, dm, eps, low):
    """x: (S, d) -> (S, d)."""
    S = x.shape[0]
    di, N, H, P, K = dm["di"], dm["N"], dm["H"], dm["P"], dm["K"]
    zxbcdt = _proj(x, p["in_proj"], ad.get("in_proj"), scale, low)
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * N],
                  zxbcdt[:, 2 * di + 2 * N:])
    w = p["conv_w"].astype(jnp.float32)                       # (ch, K)
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    conv = sum(xp[k:k + S] * w[None, :, k] for k in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32)[None, :])
    xs, Bm, Cm = xbc[:, :di], xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32)[None, :])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                # (H,)
    cum = jnp.cumsum(dt * A[None, :], axis=0)                   # (S, H)
    causal = jnp.tril(jnp.ones((S, S), bool))
    seg = jnp.where(causal[:, :, None],
                    cum[:, None, :] - cum[None, :, :], -jnp.inf)
    M = (jnp.einsum("tn,sn->ts", Cm, Bm)[:, :, None] * jnp.exp(seg)
         * dt[None, :, :])                                      # (t, s, H)
    xh = xs.reshape(S, H, P)
    y = (jnp.einsum("tsh,shp->thp", M, xh)
         + p["D"].astype(jnp.float32)[None, :, None] * xh)
    y = _round16(y.reshape(S, di), low)
    y = _rms(y * jax.nn.silu(z), p["gate_norm"], eps)
    return _proj(y, p["out_proj"], ad.get("out_proj"), scale, low)


def _attention(x, p, ad, scale, dm, mult, low):
    S = x.shape[0]
    Hq, Hk, hd = dm["heads"], dm["kv_heads"], dm["hd"]
    q = _proj(x, p["wq"], ad.get("wq"), scale, low).reshape(S, Hq, hd)
    k = _proj(x, p["wk"], ad.get("wk"), scale, low).reshape(S, Hk, hd)
    v = _proj(x, p["wv"], ad.get("wv"), scale, low).reshape(S, Hk, hd)
    k = jnp.repeat(k, Hq // Hk, axis=1)
    v = jnp.repeat(v, Hq // Hk, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) * mult
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return _proj(o.reshape(S, Hq * hd), p["wo"], ad.get("wo"), scale, low)


def _mlp(x, p, ad, scale, low):
    gu = _proj(x, p["wi"], ad.get("wi"), scale, low)
    f = gu.shape[-1] // 2
    return _proj(jax.nn.silu(gu[:, :f]) * gu[:, f:], p["wd"], ad.get("wd"),
                 scale, low)


def logits(adapters, base, tokens, c, dm, low=False):
    """Logits (S, V) of one sequence ``tokens`` (S,); ``adapters``:
    {(layer, module, weight): {"a", "b"}} (``{}``: the base model);
    ``c``: the published scalars."""
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    scale = dm["alpha"] / dm["r"]
    x = base["embed"].astype(jnp.float32)[tokens]
    x = _round16(x * c["embedding_multiplier"], low)
    li = 0
    model = load_module("models", "granite_h_lora")
    for ri, (kind, n) in enumerate(model.runs(dm["types"])):
        run = base["runs"][ri]
        for j in range(n):
            def lay(mod):
                return {k: v[j] for k, v in run[mod].items()}

            def ad(mod):
                return {w: a for (l, m, w), a in adapters.items()
                        if l == li and m == mod}

            h = _rms(x, run["ln1"]["scale"][j], eps)
            if kind == "mamba":
                m = _mamba(h, lay("ssm"), ad("ssm"), scale, dm, eps, low)
            else:
                m = _attention(h, lay("attn"), ad("attn"), scale, dm,
                               c["attention_multiplier"], low)
            x = _round16(x + r * m, low)
            h = _rms(x, run["ln2"]["scale"][j], eps)
            x = _round16(x + r * _mlp(h, lay("mlp"), ad("mlp"), scale, low),
                         low)
            li += 1
    h = _rms(x, base["final_norm"]["scale"], eps)
    return _mm(h, base["embed"].T, low) / c["logits_scaling"]


def loss(adapters, base, tokens, c, dm, low=False):
    """Mean next-token cross entropy of one sequence ``tokens`` (S,)."""
    logp = jax.nn.log_softmax(logits(adapters, base, tokens[:-1], c, dm,
                                     low), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


def unflatten(v, leaves, dm):
    """Flat adapters -> {(layer, module, weight): {"a", "b"}}
    (``leaves``: the model's ``adapter_leaves``, run by run)."""
    model = load_module("models", "granite_h_lora")
    first, li = [], 0
    for _, n in model.runs(dm["types"]):
        first.append(li)
        li += n
    out, off = {}, 0
    for ri, mod, name, ab, shape in leaves:
        size = math.prod(shape)
        arr = v[off:off + size].reshape(shape)
        off += size
        for j in range(shape[0]):
            out.setdefault((first[ri] + j, mod, name), {})[ab] = arr[j]
    return out


def batch(key, V, B, S):
    """The minibatch of ``key``: (B, S) int32 tokens."""
    ka, kb, ks, km, kn = jax.random.split(key, 5)
    a = 2 * jax.random.randint(ka, (B, 1), 1, 8) + 1
    b = jax.random.randint(kb, (B, 1), 0, V)
    start = jax.random.randint(ks, (B, 1), 0, V)
    t = jnp.arange(S)[None, :]
    toks = (start + a * t + b * (t // 7)) % V
    noise = jax.random.uniform(km, (B, S)) < 0.05
    return jnp.where(noise, jax.random.randint(kn, (B, S), 0, V), toks)


def client_loss(v, base, key, leaves, dm, c, low):
    """Mean loss over the minibatch of ``key`` at flat adapters v."""
    ad = unflatten(v, leaves, dm)
    toks = batch(key, dm["V"], dm["B"], dm["S"])
    return jnp.mean(jax.vmap(lambda t: loss(ad, base, t, c, dm, low))(toks))


SCALARS = ("rms_norm_eps", "residual_multiplier", "embedding_multiplier",
           "attention_multiplier", "logits_scaling")


def statics(config, low=False):
    """The configuration's hashable part a jitted step needs."""
    model = load_module("models", config["model"])
    dm = model.dims(config)
    c = {k: float(config[k]) for k in SCALARS}
    return (tuple(model.adapter_leaves(config)), tuple(sorted(dm.items())),
            tuple(sorted(c.items())), bool(low))


def _unpack(st):
    leaves, dm_items, c_items, low = st
    return leaves, dict(dm_items), dict(c_items), low


@functools.partial(jax.jit, static_argnames=("st",))
def grad(v, base, key, *, st):
    """The gradient of ``client_loss`` at flat adapters v, in float32
    at the highest matmul precision (bfloat16 places under ``low``)."""
    leaves, dm, c, low = _unpack(st)
    with jax.default_matmul_precision("highest"):
        return jax.grad(client_loss)(v, base, key, leaves, dm, c, low)


@functools.partial(jax.jit, static_argnames=("st", "s"))
def _round(v, base, ckeys, k, eta, *, st, s):
    leaves, dm, c, low = _unpack(st)

    def one(ck):
        rk = jax.random.fold_in(ck, k)
        w = v
        U = jnp.zeros_like(v)
        for j in range(s):
            g = jax.grad(client_loss)(w, base, jax.random.fold_in(rk, j),
                                      leaves, dm, c, low)
            U = _round16(U + g, low)
            w = _round16(w - eta * g, low)
        return U

    with jax.default_matmul_precision("highest"):
        Us = jax.lax.map(one, ckeys)
    return _round16(v - eta * jnp.sum(Us, axis=0), low)


def run(config, traffic, job, inputs, v0, seed, low=False):
    fleet, fl = traffic["fleet"], traffic["fl"]
    closed_form = (
        int(fl["d"]) == 1 and not config.get("dp")
        and fl.get("aggregation") in (None, "paper")
        and traffic["sizes"]["kind"] == "constant"
        and fleet["speeds"]["kind"] == "equal"
        and fleet["availability"]["kind"] == "always"
        and int(fleet.get("latency_ticks", 1)) == 1)
    if not closed_form:
        raise ValueError("the fedavg closed form holds only for constant "
                         "s, d = 1, no DP, the paper's aggregation and an "
                         "equal, always-on fleet with one tick of latency")
    s = int(traffic["sizes"]["s"])
    plan = job["plan"]
    R, C = int(plan["rounds"]), int(config["clients"])
    base_key = jax.random.PRNGKey(seed32(seed))
    ckeys = jax.vmap(lambda i: jax.random.fold_in(base_key, i))(
        jnp.arange(C))
    st = statics(config, low)
    v = _round16(jnp.asarray(v0, jnp.float32), low)
    etas = np.asarray(plan["etas"], np.float32)
    for k in range(R):
        v = _round(v, inputs["base"], ckeys, jnp.int32(k), etas[k], st=st,
                   s=s)
    block = int(fl["cohort_block"])
    return {"v": np.asarray(v),
            "census": {"messages": C * R, "broadcasts": R,
                       "ticks": R * (math.ceil(s / block) + 1)}}
