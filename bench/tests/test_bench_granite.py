"""The granite LoRA cell at a small size on the CPU: a run reads
correct, a run whose timed path is broken underneath reads not correct,
the control (the reference one precision step down) reads not correct,
and ``work()`` counts what a step needs."""
import io
import math

import jax
import jax.numpy as jnp
import pytest

import repro.cohort.device as device
import repro.models.lora as lora
import repro.models.transformer as transformer
from control import readings
from yardstick.measure import run, sized
from yardstick.spec import Cell, load_benchmark, model_of

CELL = "granite_h_micro_lora_fedavg"
#: published layer order and ratios at a CPU's width (bfloat16 base, as
#: configured); ``test_bench_cells``' logreg size would be the
#: published widths here
SMALL = {"clients": 4, "hidden_size": 64, "num_attention_heads": 2,
         "num_key_value_heads": 1, "mamba_n_heads": 4, "mamba_d_head": 32,
         "mamba_d_state": 16, "mamba_chunk_size": 16,
         "shared_intermediate_size": 128, "vocab_size": 256, "seq_len": 32,
         "lora_rank": 4, "lora_alpha": 8, "rounds_per_job": 3,
         "warmup_rounds": 1}
SEED = 2 ** 31 + 211


def small_run(seed=SEED, trace=False):
    return run(CELL, seed, 0.01, trace, require_tpu=False, size=SMALL,
               out=io.StringIO(), compile_cache=False)


def test_cell_is_declared_with_its_metrics():
    bm = load_benchmark()
    (cfg,) = [c for c in bm["configs"]
              if c["name"] == "granite4_h_micro_lora_c16"]
    assert cfg["reduced"] == ["layers"]
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.config["layers"] == 10
    assert model_of(cell.config).width(cell.config) == 7_205_888
    assert {m["name"] for m in cell.per_layer()} == {"hybrid_step_mfu",
                                                     "hybrid_xla_mfu"}


def test_small_run_is_correct():
    res = small_run()
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["census_mismatch"]["value"] == 0
    assert set(res["metrics"]) == {"updates_per_s", "round_ms_p95",
                                   "setup_s"}


def skip_attention():
    """The attention layer's mixer adds nothing: its residual branch is
    zero."""
    def f(cfg, lp, h, *a):
        return jnp.zeros_like(h)
    return transformer, "_attention_mixer", f


def one_client_left_out():
    """Client 0's update never reaches the server's bucket."""
    orig = device.tick_scatter

    def f(sent, w, U, upd, wgt, any_g, done, eta, **kw):
        keep = (jnp.arange(wgt.shape[1]) > 0).astype(wgt.dtype)
        return orig(sent, w, U, upd, wgt * keep[None, :], any_g, done, eta,
                    **kw)
    return device, "tick_scatter", f


def float8_base():
    """The frozen base's matmuls take float8 (e4m3) inputs."""
    orig, f8 = lora.linear, jnp.float8_e4m3fn

    def f(x, w, adapter=None, scale=1.0):
        y = jnp.einsum("...i,io->...o", x.astype(f8), w.astype(f8),
                       preferred_element_type=jnp.float32)
        if adapter is None:
            return y
        return y + (orig(x, w, adapter, scale) - orig(x, w))
    return lora, "linear", f


@pytest.mark.parametrize("fault", [skip_attention, one_client_left_out,
                                   float8_base], ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(*fault())
    res = small_run(SEED + 1)
    assert res["correct"] is False, res["checks"]
    gap = res["checks"]["model_gap"]
    assert gap["value"] > gap["limit"]


def test_control_is_not_correct():
    (row,) = readings(CELL, [SEED + 2], SMALL, require_tpu=False)
    assert row["correct"] is False, row
    assert row["checks"]["census_mismatch"]["value"] == 0
    gap = row["checks"]["model_gap"]
    assert gap["value"] > gap["limit"]


def test_work_at_published_widths_by_hand():
    config = Cell(CELL).config
    work = model_of(config).work(config)
    d, V, f, r = 2048, 100352, 8192, 16
    mamba = {"in_proj": (d, 2 * 4096 + 2 * 128 + 64), "out_proj": (4096, d),
             "wi": (d, 2 * f), "wd": (f, d)}
    attn = {"wq": (d, 2048), "wk": (d, 512), "wv": (d, 512), "wo": (2048, d),
            "wi": (d, 2 * f), "wd": (f, d)}
    base = 9 * sum(i * o for i, o in mamba.values()) + sum(
        i * o for i, o in attn.values()) + d * V
    ad = 9 * sum(i + o for i, o in mamba.values()) + sum(
        i + o for i, o in attn.values())
    ssd = 9 * 2 * 64 * 128 * 64          # state update and read-out
    att = 32 * 64 * 512                  # scores and values, causal
    first_dx = d * (2 * 4096 + 2 * 128 + 64) + r * d
    step = 2 * (2 * base) + 3 * 2 * (r * ad + ssd + att) - 2 * first_dx
    assert work["token_flops"] == step
    assert 3.85e9 < step < 3.95e9
    assert work["step_flops"] == 512 * step
    forward = 2 * base + 2 * (r * ad + ssd + att)
    assert work["eval_flops"] == 512 * forward


def test_work_against_xla_cost_at_a_small_size():
    """XLA's count of the un-rematerialised step (layers and loops
    unrolled, so each trip counts) holds the necessary matmul FLOPs
    plus elementwise work and the chunked SSD's extra: work() reads
    80-100% of it."""
    config, _ = sized(Cell(CELL), {
        **SMALL, "hidden_size": 256, "num_attention_heads": 4,
        "mamba_n_heads": 8, "mamba_d_head": 64, "mamba_d_state": 32,
        "shared_intermediate_size": 512, "vocab_size": 1024,
        "seq_len": 64})
    model = model_of(config)
    inputs = model.inputs(3, config)
    task, ctask = model.build(config, inputs, 3)
    v = jnp.asarray(model.initial_vector(inputs))
    batch = task.data_fn(0, 0, 0)

    def loss(vec, base):
        return transformer.pattern_loss(task.cfg, base, batch,
                                        ctask.unflatten(vec), remat=False,
                                        unroll=True)

    def xla_flops(fn):
        cost = jax.jit(fn).lower(v, task.frozen).compile().cost_analysis()
        return (cost[0] if isinstance(cost, list) else cost)["flops"]

    work = model.work(config)
    assert 0.8 < work["step_flops"] / xla_flops(jax.grad(loss)) <= 1.0
    assert 0.8 < work["eval_flops"] / xla_flops(loss) <= 1.0
    assert math.isclose(work["tokens_per_step"], 64)
