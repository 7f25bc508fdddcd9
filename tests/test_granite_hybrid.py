"""granite-4.0-h-micro (Mamba-2 + NoPE GQA hybrid) under LoRA, against
the benchmark's plain float32 reference (``bench/references/
granite_h_lora_fedavg.py``), on seeded random weights at a small width
that keeps the published period's layer order (9 Mamba-2 layers,
attention at position 5).

The base is drawn by the benchmark's model (``bench/models/
granite_h_lora.py``) and cast to float32, so the program computes in
float32 here (each projection computes in its weight's dtype) and every
comparison is tight: what is left is the chunked SSD against the
reference's full SSM matrix and the order of float sums."""
import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                     "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from yardstick.measure import sized  # noqa: E402
from yardstick.spec import Cell, model_of, reference_of  # noqa: E402

from repro.cohort import CohortEngine, make_simulator  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import FLConfig  # noqa: E402
from repro.configs.granite_4_h_micro import first_layers  # noqa: E402
from repro.models import init_params, transformer  # noqa: E402
from repro.models.lora import init_lora  # noqa: E402

CELL = "granite_h_micro_lora_fedavg"
SMALL = {"clients": 4, "hidden_size": 64, "num_attention_heads": 2,
         "num_key_value_heads": 1, "mamba_n_heads": 4, "mamba_d_head": 32,
         "mamba_d_state": 16, "mamba_chunk_size": 16,
         "shared_intermediate_size": 128, "vocab_size": 256, "seq_len": 32,
         "lora_rank": 4, "lora_alpha": 8}
SEED = 12345
#: float32 on both sides: logits and loss to 1e-5 of their scale, the
#: gradients to 1e-4 (the chunked SSD and the full SSM matrix sum in
#: different orders; every mutation below moves them by 1e-2 or more)
TOL_LOGITS, TOL_GRAD, TOL_ENGINE = 1e-5, 1e-4, 1e-4


def _setup(size=None, f32=True):
    config, traffic = sized(Cell(CELL), {**SMALL, **(size or {})})
    model = model_of(config)
    inputs = model.inputs(SEED, config)
    if f32:
        inputs = dict(inputs, base=jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), inputs["base"]))
    task, ctask = model.build(config, inputs, SEED)
    return config, traffic, model, reference_of(config, traffic), inputs, \
        task, ctask


def _nonzero_b(ctask, model, inputs):
    """The seed's adapters with B drawn too (a fresh B is zero, and then
    A has no gradient)."""
    v0 = jnp.asarray(model.initial_vector(inputs))
    return v0 + 0.05 * jax.random.normal(jax.random.PRNGKey(7), v0.shape)


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def test_config_is_the_published_one():
    cfg = get_config("granite-4.0-h-micro")
    assert cfg.layer_types.count("attention") == 4
    assert [i for i, t in enumerate(cfg.layer_types)
            if t == "attention"] == [5, 15, 25, 35]
    assert cfg.param_count() == 3_191_396_096
    period = first_layers(10)
    assert period.param_count() == 951_991_232
    assert transformer.layer_runs(period) == [("mamba", 5),
                                              ("attention", 1),
                                              ("mamba", 4)]
    # rank-16 adapters on every projection of the period: D = 7,205,888,
    # B zero (shapes only, nothing drawn at this size)
    base = jax.eval_shape(lambda k: init_params(period, k),
                          jax.random.PRNGKey(0))
    ad = jax.eval_shape(lambda k: init_lora(base["runs"], k),
                        jax.random.PRNGKey(1))
    assert sum(x.size for x in jax.tree_util.tree_leaves(ad)) == 7_205_888
    small = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        init_params(dataclasses.replace(period, d_model=64, n_heads=2,
                                        n_kv_heads=1, head_dim=32, d_ff=128,
                                        vocab_size=256, ssm_state=16,
                                        ssm_head_dim=32),
                    jax.random.PRNGKey(0)))
    fresh = init_lora(small["runs"], jax.random.PRNGKey(1))
    assert fresh.scale == 2.0
    for run in fresh.layers:
        for mod in run.values():
            for pair in mod.values():
                assert not np.any(np.asarray(pair["b"]))
                assert np.any(np.asarray(pair["a"]))


def test_logits_loss_and_lora_grads_match_reference():
    config, _, model, ref, inputs, task, ctask = _setup()
    v = _nonzero_b(ctask, model, inputs)
    st = ref.statics(config)
    leaves, dm, c, _ = ref._unpack(st)
    key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(SEED), 1), 0), 0)
    toks = task.data_fn(1, 0, 0)["tokens"]
    np.testing.assert_array_equal(
        np.asarray(ref.batch(key, dm["V"], dm["B"], dm["S"])),
        np.asarray(toks))
    adapters = ctask.unflatten(v)
    hidden, _ = transformer.forward_pattern(
        task.cfg, task.frozen, toks, adapters=adapters)
    got = transformer.pattern_logits(task.cfg, task.frozen, hidden)[0]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda vec, base, t: ref.logits(
            ref.unflatten(vec, leaves, dm), base, t, c, dm))(
                v, inputs["base"], toks[0])
        want_loss = jax.jit(lambda vec, base, k: ref.client_loss(
            vec, base, k, leaves, dm, c, False))(v, inputs["base"], key)
    assert _rel(got, want) < TOL_LOGITS
    loss = task.loss(adapters, {"tokens": toks}, task.frozen)
    assert abs(float(loss) - float(want_loss)) < TOL_LOGITS * float(
        want_loss)
    g = jax.grad(lambda vec: task.loss(ctask.unflatten(vec),
                                       {"tokens": toks}, task.frozen))(v)
    g_ref = ref.grad(v, inputs["base"], key, st=st)
    assert _rel(g, g_ref) < TOL_GRAD


def test_zero_adapters_give_the_base_model():
    config, _, model, ref, inputs, task, ctask = _setup()
    _, dm, c, _ = ref._unpack(ref.statics(config))
    toks = task.data_fn(0, 0, 0)["tokens"]
    fresh = ctask.unflatten(jnp.asarray(model.initial_vector(inputs)))

    def logits(adapters):
        h, _ = transformer.forward_pattern(task.cfg, task.frozen, toks,
                                           adapters=adapters)
        return transformer.pattern_logits(task.cfg, task.frozen, h)[0]

    with_zero_b = logits(fresh)
    np.testing.assert_array_equal(np.asarray(with_zero_b),
                                  np.asarray(logits(None)))
    with jax.default_matmul_precision("highest"):
        base = jax.jit(lambda b, t: ref.logits({}, b, t, c, dm))(
            inputs["base"], toks[0])
    assert _rel(with_zero_b, base) < TOL_LOGITS


def test_device_engine_matches_fedavg_closed_form():
    """Three rounds of make_simulator(engine="device") over the
    frozen-base task against the protocol's closed form."""
    config, traffic, model, ref, inputs, task, ctask = _setup()
    R, C = 3, ctask.C
    etas = [0.5 / C] * R
    sim = make_simulator(FLConfig(engine="device", **traffic["fl"]), ctask,
                         n_clients=C, sizes_per_client=[2] * R,
                         round_stepsizes=etas, d=1, seed=SEED)
    res = sim.run(max_rounds=R, eval_every=1)
    v0 = model.initial_vector(inputs)
    job = {"plan": {"rounds": R, "etas": etas, "sizes": [2] * R}}
    want = ref.run(config, traffic, job, inputs, jnp.asarray(v0), SEED)
    v = np.asarray(sim.engine.state.v)
    change = float(np.abs(want["v"] - v0).max())
    assert change > 0
    assert float(np.abs(v - want["v"]).max()) / change < TOL_ENGINE
    tel = res["telemetry"]
    assert {"messages": tel.messages, "broadcasts": tel.broadcasts,
            "ticks": tel.ticks} == want["census"]
    # the census counts the tokens of every step the block took
    assert tel.ops["client_tokens"] == C * 2 * R * ctask.tokens_per_step


def test_frozen_base_is_an_operand_not_a_constant():
    """The compiled segment of a frozen-base task holds no constant of
    1 MB or more, though the base here has a 4 MB embedding table."""
    _, traffic, _, _, _, task, ctask = _setup({"vocab_size": 16384})
    emb = task.frozen["embed"]
    assert emb.size * emb.dtype.itemsize >= 4 << 20
    sim = make_simulator(FLConfig(engine="device", **traffic["fl"]), ctask,
                         n_clients=ctask.C, sizes_per_client=[2],
                         round_stepsizes=[0.01], d=1, seed=SEED)
    hlo = sim.engine.segment_hlo()
    sizes = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1, "f16": 2}
    biggest = 0
    for dt, dims in re.findall(r"= (\w+)\[([\d,]*)\][^=]*? constant\(",
                               hlo):
        n = int(np.prod([int(x) for x in dims.split(",") if x]))
        biggest = max(biggest, n * sizes.get(dt, 8))
    assert biggest < 1 << 20, biggest


def test_host_engine_refuses_a_frozen_base():
    _, _, _, _, _, _, ctask = _setup()
    with pytest.raises(ValueError, match="frozen base"):
        CohortEngine(ctask, sizes_per_client=[2], round_stepsizes=[0.1])
