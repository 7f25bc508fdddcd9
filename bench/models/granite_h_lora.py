"""granite-4.0-h-micro under federated LoRA fine-tuning, the
configurations' ``"model": "granite_h_lora"``.

    inputs(seed, config)    the frozen base (bfloat16) and the initial
                            adapters (float32), drawn on the device from
                            the seed
    width(config)           D, the adapters' width (B included)
    initial_vector(inputs)  the flat adapters: A from the seed, B zero
    work(config)            necessary FLOPs and f32 rows of one client
                            step and of one eval (``yardstick/counts``)
    build(config, inputs, seed)
                            the program's frozen-base task and cohort
                            task: the system under test

The configuration holds the published ``config.json`` keys (hidden_size,
mamba_*, layer_types, the multipliers, ...) and the cut: ``layers``, the
first layers of ``layer_types`` held; ``clients``; ``seq_len`` and
``batch`` of a client's minibatch; ``lora_rank``, ``lora_alpha`` and
``lora_targets`` ({module: [weight, ...]}).

Layout of the base, as the program takes it: ``embed`` [V, d],
``final_norm`` {"scale": [d]}, and ``runs``, one dict per maximal run of
same-kind layers in order, each leaf stacked over the run's layers:
``ln1``, ``ln2`` ({"scale": [n, d]}), ``mlp`` (``wi`` [n, d, 2 f] gate |
up, ``wd`` [n, f, d]), and ``ssm`` (``in_proj`` [n, d, 2 di + 2 N + H]
giving z | x | B | C | dt, ``conv_w`` [n, di + 2 N, K], ``conv_b``,
``dt_bias``, ``A_log``, ``D``, ``gate_norm``, ``out_proj`` [n, di, d])
or ``attn`` (``wq``, ``wk``, ``wv`` [n, d, heads x 64], ``wo``).  Every
weight is used as ``x @ W``.  Draws: embeddings N(0, 0.02), projections
N(0, 1/fan-in); Mamba-2's own initialisation of the SSM (dt log-uniform
in [1e-3, 1e-1] through ``dt_bias``, A = -exp(``A_log``) uniform in
[-16, -1]), the conv and its bias uniform in +-1/sqrt(K) (PyTorch's
Conv1d); norms and ``D`` one.  LoRA's A is uniform in +-1/sqrt(in).

The flat adapter vector (``adapter_leaves``): run by run, each run's
modules in sorted name order, their weights in sorted order, A then B,
each leaf row-major over (layer of the run, in, r) or (layer, r, out):
the order the program's ``PyTreeFlattener`` ravels its ``LoRA``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from yardstick.data import seed32


def _program_config():
    """The program's granite config (an ImportError on a checkout
    without it, before anything is drawn)."""
    from yardstick.program import import_program
    import_program()
    from repro.configs import granite_4_h_micro
    return granite_4_h_micro


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The widths a run uses, from the configuration's keys."""
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    out = {
        "d": d, "V": int(config["vocab_size"]),
        "heads": heads, "kv_heads": int(config["num_key_value_heads"]),
        "hd": d // heads, "f": int(config["shared_intermediate_size"]),
        "di": int(config["mamba_expand"]) * d,
        "H": int(config["mamba_n_heads"]), "P": int(config["mamba_d_head"]),
        "N": int(config["mamba_d_state"]), "K": int(config["mamba_d_conv"]),
        "G": int(config["mamba_n_groups"]),
        "types": tuple(config["layer_types"][:int(config["layers"])]),
        "r": int(config["lora_rank"]), "alpha": float(config["lora_alpha"]),
        "S": int(config["seq_len"]), "B": int(config["batch"]),
        "C": int(config["clients"])}
    if out["H"] * out["P"] != out["di"] or out["G"] != 1:
        raise ValueError("mamba_n_heads x mamba_d_head must be "
                         "mamba_expand x hidden_size, with one group")
    return out


def runs(types) -> List[Tuple[str, int]]:
    """[(kind, n)]: maximal runs of same-kind layers, in order."""
    out: List[List] = []
    for t in types:
        if out and out[-1][0] == t:
            out[-1][1] += 1
        else:
            out.append([t, 1])
    return [(k, n) for k, n in out]


def weight_shapes(dm: Dict[str, Any], kind: str) -> Dict[str, Dict]:
    """{module: {weight: (in, out)}} of one layer's projections."""
    d, di, N, H = dm["d"], dm["di"], dm["N"], dm["H"]
    q, kv = dm["heads"] * dm["hd"], dm["kv_heads"] * dm["hd"]
    out = {"mlp": {"wi": (d, 2 * dm["f"]), "wd": (dm["f"], d)}}
    if kind == "mamba":
        out["ssm"] = {"in_proj": (d, 2 * di + 2 * N + H),
                      "out_proj": (di, d)}
    else:
        out["attn"] = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                       "wo": (q, d)}
    return out


def adapter_leaves(config: Dict[str, Any]):
    """[(run, module, weight, "a" | "b", shape)] in flat order."""
    dm = dims(config)
    targets = config["lora_targets"]
    out = []
    for ri, (kind, n) in enumerate(runs(dm["types"])):
        ws = weight_shapes(dm, kind)
        for mod in sorted(ws):
            for name in sorted(ws[mod]):
                if name not in targets.get(mod, ()):
                    continue
                i, o = ws[mod][name]
                out.append((ri, mod, name, "a", (n, i, dm["r"])))
                out.append((ri, mod, name, "b", (n, dm["r"], o)))
    return out


def width(config: Dict[str, Any]) -> int:
    return int(sum(math.prod(s) for *_, s in adapter_leaves(config)))


def _draw(key, config):
    """(base, adapters' leaves) from ``key``, as the module docstring
    lays them out."""
    dm = dims(config)
    bf = jnp.bfloat16
    d = dm["d"]
    kinds = runs(dm["types"])
    k_embed, k_runs, k_ad = jax.random.split(key, 3)

    def normal(k, shape, std):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(bf)

    def uniform(k, shape, bound):
        return jax.random.uniform(k, shape, jnp.float32, -bound,
                                  bound).astype(bf)

    def ones(shape):
        return jnp.ones(shape, bf)

    base = {"embed": normal(k_embed, (dm["V"], d), 0.02),
            "final_norm": {"scale": ones((d,))}, "runs": []}
    for (kind, n), kr in zip(kinds, jax.random.split(k_runs, len(kinds))):
        ws = weight_shapes(dm, kind)
        keys = iter(jax.random.split(kr, 8))
        run = {"ln1": {"scale": ones((n, d))}, "ln2": {"scale": ones((n, d))}}
        for mod in sorted(ws):
            run[mod] = {name: normal(next(keys), (n, i, o), 1 / math.sqrt(i))
                        for name, (i, o) in sorted(ws[mod].items())}
        if kind == "mamba":
            conv, H, K = dm["di"] + 2 * dm["N"], dm["H"], dm["K"]
            kc, kb, ka, kt = jax.random.split(next(keys), 4)
            # Mamba-2's own initialisation: dt log-uniform in [1e-3,
            # 1e-1] through softplus, A uniform in [1, 16]; the conv as
            # PyTorch's Conv1d, uniform in +-1/sqrt(K)
            dt = jnp.exp(jax.random.uniform(
                kt, (n, H), jnp.float32, math.log(1e-3), math.log(1e-1)))
            run["ssm"].update(
                conv_w=uniform(kc, (n, conv, K), 1 / math.sqrt(K)),
                conv_b=uniform(kb, (n, conv), 1 / math.sqrt(K)),
                dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(bf),
                A_log=jnp.log(jax.random.uniform(ka, (n, H), jnp.float32,
                                                 1.0, 16.0)).astype(bf),
                D=ones((n, H)), gate_norm=ones((n, dm["di"])))
        base["runs"].append(run)
    base["runs"] = tuple(base["runs"])
    leaves = adapter_leaves(config)
    ad = []
    for (ri, mod, name, ab, shape), k in zip(
            leaves, jax.random.split(k_ad, len(leaves))):
        bound = 1.0 / math.sqrt(shape[1])
        ad.append(jax.random.uniform(k, shape, jnp.float32, -bound, bound)
                  if ab == "a" else jnp.zeros(shape, jnp.float32))
    return base, tuple(ad)


#: the largest base drawn on a CPU backend, which runs the benchmark's
#: tests at small sizes only: a whole period (1.9 GB, a 64-client job of
#: ~4 PFLOP) is the chip's
CPU_BASE_BYTES = 2 ** 28


def inputs(seed: int, config: Dict[str, Any]):
    """-> {"base": the frozen base (bfloat16), "adapters": the adapters'
    leaves in flat order (float32: each A from the seed, each B zero)},
    on the default device."""
    _program_config()
    if (jax.default_backend() == "cpu"
            and base_bytes(config) > CPU_BASE_BYTES):
        raise ValueError(
            f"a {base_bytes(config) / 1e9:.2f} GB base on a CPU backend: "
            "this configuration runs on its chip; a test sets a small size")
    base, ad = jax.jit(lambda k: _draw(k, config))(
        jax.random.PRNGKey(seed32(seed)))
    return {"base": base, "adapters": ad}


def initial_vector(inputs_) -> np.ndarray:
    return np.concatenate([np.asarray(x).reshape(-1)
                           for x in inputs_["adapters"]])


def token_flops(config: Dict[str, Any]) -> Dict[str, float]:
    """Necessary FLOPs of one token, forward and a training step (the
    forward, the activation gradients and the adapters' gradients; no
    recomputation).  Matmuls count 2 per multiply-add: each projection
    2 in x out, its adapter 2 r (in + out); attention's scores and
    values 2 x 2 heads x hd x (S' + 1) / 2 causally (S' = seq_len - 1
    positions); the SSD's recurrence 2 x 2 H N P (state update, read-
    out).  A training step is three times the attention, the SSD and
    the adapters (their forward, their input and their operand
    gradients) and twice the frozen projections and the unembedding
    (forward, input gradient), less the first layer's input gradient,
    which the frozen embedding does not need."""
    dm = dims(config)
    r, S1 = dm["r"], dm["S"] - 1
    targets = config["lora_targets"]
    fwd_base = 2.0 * dm["d"] * dm["V"]
    fwd_lora = fwd_mix = 0.0
    first_dx = None
    for kind in dm["types"]:
        ws = weight_shapes(dm, kind)
        for mod, names in ws.items():
            for name, (i, o) in names.items():
                fwd_base += 2.0 * i * o
                if name in targets.get(mod, ()):
                    fwd_lora += 2.0 * r * (i + o)
        if kind == "mamba":
            fwd_mix += 4.0 * dm["H"] * dm["N"] * dm["P"]
        else:
            fwd_mix += 2.0 * dm["heads"] * dm["hd"] * (S1 + 1)
        if first_dx is None:
            mod, name = (("ssm", "in_proj") if kind == "mamba"
                         else ("attn", None))
            if name is None:
                first_dx = sum(2.0 * i * o + (2.0 * r * i if n in
                                               targets.get(mod, ()) else 0)
                               for n, (i, o) in ws[mod].items()
                               if n != "wo")
            else:
                i, o = ws[mod][name]
                first_dx = 2.0 * i * o + (2.0 * r * i if name in
                                          targets.get(mod, ()) else 0)
    fwd = fwd_base + fwd_lora + fwd_mix
    step = 2 * fwd_base + 3 * (fwd_lora + fwd_mix) - first_dx
    return {"forward": fwd, "step": step}


def work(config: Dict[str, Any]) -> Dict[str, float]:
    """A client step: ``batch`` x ``seq_len`` tokens at the step's FLOPs
    a token; it reads the client's adapters and reads and writes its
    update (3 rows of D), and the cohort reads the frozen base twice a
    step (forward, backward), shared by the C clients.  An eval: the
    forward of one minibatch, reading the base and the server's row."""
    dm, D = dims(config), width(config)
    tf = token_flops(config)
    tokens = dm["B"] * dm["S"]
    base_rows = base_bytes(config) / (4.0 * D)
    return {"step_flops": tokens * tf["step"], "eval_flops":
            tokens * tf["forward"],
            "step_rows": 3 + 2 * base_rows / dm["C"],
            "eval_rows": 1 + base_rows,
            "token_flops": tf["step"], "tokens_per_step": tokens}


def base_bytes(config: Dict[str, Any]) -> float:
    """Bytes of the bfloat16 base."""
    dm = dims(config)
    n = dm["V"] * dm["d"] + dm["d"]
    for kind in dm["types"]:
        n += sum(i * o for ws in weight_shapes(dm, kind).values()
                 for i, o in ws.values()) + 2 * dm["d"]
        if kind == "mamba":
            conv = dm["di"] + 2 * dm["N"]
            n += conv * (dm["K"] + 1) + 3 * dm["H"] + dm["di"]
    return 2.0 * n


def model_config(config: Dict[str, Any]):
    """The program's ModelConfig: granite-4.0-h-micro with the
    configuration's widths and its first ``layers`` layers."""
    import dataclasses
    g = _program_config()
    dm = dims(config)
    return dataclasses.replace(
        g.config(), n_layers=len(dm["types"]), layer_types=dm["types"],
        d_model=dm["d"], vocab_size=dm["V"], n_heads=dm["heads"],
        n_kv_heads=dm["kv_heads"], head_dim=dm["hd"], d_ff=dm["f"],
        ssm_state=dm["N"], ssm_expand=int(config["mamba_expand"]),
        ssm_head_dim=dm["P"], ssm_conv_width=dm["K"],
        ssm_chunk=int(config["mamba_chunk_size"]),
        ssm_n_groups=dm["G"], ssm_conv_bias=bool(config["mamba_conv_bias"]),
        attention_multiplier=float(config["attention_multiplier"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        norm_eps=float(config["rms_norm_eps"]),
        position_embedding=str(config["position_embedding_type"]))


def build(config: Dict[str, Any], inputs_, seed: int):
    """-> (task, cohort task): the program's LoRA task over the frozen
    base, its initial adapters the ones drawn from the seed."""
    from repro.cohort import CohortBatchModelTask
    from repro.core import BatchModelTask
    from repro.data import SeedAddressedBatcher
    from repro.models.lora import LoRA

    dm = dims(config)
    cfg = model_config(config)
    arrays = iter(inputs_["adapters"])
    layers = []
    for ri, (kind, n) in enumerate(runs(dm["types"])):
        ad: Dict[str, Dict] = {}
        for r, mod, name, ab, _ in adapter_leaves(config):
            if r == ri:
                ad.setdefault(mod, {}).setdefault(name, {})[ab] = next(arrays)
        layers.append(ad)
    adapters = LoRA(tuple(layers), dm["r"], dm["alpha"])
    batcher = SeedAddressedBatcher(cfg, batch_size=dm["B"],
                                   seq_len=dm["S"], seed=seed)
    task = BatchModelTask(cfg, adapters, batcher, frozen=inputs_["base"])
    ctask = CohortBatchModelTask(task, dm["C"], seed=seed)
    want = [s for *_, s in adapter_leaves(config)]
    if list(ctask.flattener.shapes) != want:
        raise ValueError("the program ravels the adapters in another "
                         f"order: {ctask.flattener.shapes} != {want}")
    return task, ctask
