"""Device scopes (``repro.telemetry.scopes``) and the engine's host spans.

The device engine's compiled segment carries each part of the tick in
its ``op_name`` metadata; ``op_scopes`` reads it back per instruction.
The scopes are metadata only: the goldens and the parity tests pin that
they change no number."""
import numpy as np
import pytest

from repro.cohort import DeviceCohortSimulator
from repro.cohort.device import DeviceCohortEngine
from repro.core import LogRegTask
from repro.data import make_binary_dataset
from repro.telemetry import DEVICE_SCOPES, SpanRecorder, op_scopes
from repro.telemetry.scopes import (ALLREDUCE_SCOPE, KERNEL_SCOPES,
                                    SEGMENT_SCOPES, scope_path)


def _sim(dp, scenario, **kw):
    X, y = make_binary_dataset(300, 12, seed=7, noise=0.3)
    task = LogRegTask(X, y, l2=1.0 / 300, sample_seed=21,
                      dp_clip=1.0 if dp else 0.0,
                      dp_sigma=1.5 if dp else 0.0)
    return DeviceCohortSimulator(
        task, n_clients=6, sizes_per_client=[4, 6, 8],
        round_stepsizes=[0.1, 0.08, 0.06], d=2, seed=3, block=4,
        scenario=scenario, **kw)


def test_scope_path_and_op_scopes_by_hand():
    assert scope_path("jit(segment)/while/body/cohort.segment/cond/"
                      "branch_1_fun/cohort.complete/jit(tick_scatter)/"
                      "tick_scatter/pad") == (
        "cohort.segment/cohort.complete/tick_scatter")
    assert scope_path("jit(segment)/while/body/add") == ""
    hlo = "\n".join([
        "ENTRY %main {",
        '  %fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
        'calls=%fused_computation, metadata={op_name="jit(segment)/'
        'while/body/cohort.segment/cohort.client_block/mul" '
        'source_file="x.py" source_line=3}',
        '  %copy.3 = f32[8]{0} copy(f32[8]{0} %fusion.12)',
        '  ROOT %tick_deliver.1 = f32[8]{0} custom-call(f32[8]{0} %copy.3)'
        ', custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(segment)/cohort.segment/cohort.deliver/jit(tick_deliver)/'
        'tick_deliver/tick_deliver/pallas_call"}',
        '  %add.2 = f32[8]{0} add(%a, %b), metadata={op_name="jit(f)/add"}',
        "}"])
    assert op_scopes(hlo) == {
        "fusion.12": "cohort.segment/cohort.client_block",
        "tick_deliver.1": "cohort.segment/cohort.deliver/tick_deliver"}


# on CPU the client block runs its jnp reference (under
# cohort.client_block), not the kernel wrapper client_block_sgd
CPU_KERNEL_SCOPES = tuple(k for k in KERNEL_SCOPES if k != "client_block_sgd")


@pytest.mark.parametrize("dp,scenario,want", [
    (True, "mobile_diurnal", SEGMENT_SCOPES + CPU_KERNEL_SCOPES),
    (False, "uniform",
     SEGMENT_SCOPES + tuple(k for k in CPU_KERNEL_SCOPES
                            if k != "cohort_clip_noise")),
])
def test_compiled_segment_carries_every_scope(dp, scenario, want):
    sim = _sim(dp, scenario)
    sim.run(max_rounds=2)
    table = sim.engine.segment_scopes()
    seen = {c for path in table.values() for c in path.split("/")}
    assert set(want) <= seen, sorted(set(want) - seen)
    assert seen <= set(DEVICE_SCOPES)
    assert ALLREDUCE_SCOPE not in seen      # one device: no all-reduce
    # the table comes from the executable the run used: no new compile
    assert sim.engine.compiles == 1


def test_segment_cache_counts_misses_only():
    a = _sim(False, "uniform")
    a.run(max_rounds=2)
    # a second engine over the same cohort task reuses the executable
    eng = DeviceCohortEngine(
        a.ctask, sizes_per_client=[4, 6, 8],
        round_stepsizes=[0.1, 0.08, 0.06], d=2, seed=3, block=4,
        scenario="uniform")
    res = eng.run(max_rounds=2)
    assert eng.compiles == 0 and res["telemetry"].wall["compiles"] == 0
    assert "compile_s" not in res["telemetry"].wall
    np.testing.assert_array_equal(np.asarray(eng.state.v),
                                  np.asarray(a.engine.state.v))
    # the first run's wall holds the construction; a continued run
    # reports its own spans only
    seen = len(a.engine.history)
    again = a.engine.run(max_rounds=3)["telemetry"].wall
    assert "engine_init_s" not in again and again["compiles"] == 0
    # this run's evals: those it added to the history, and the final one
    assert again["eval_n"] == len(a.engine.history) - seen + 1


def test_engine_spans_nest():
    sim = _sim(True, "mobile_diurnal")
    sim.run(max_rounds=2)
    spans = sim.engine.timer.spans

    def within(child, parent):
        ps = [s for s in spans if s["name"] == parent]
        for c in (s for s in spans if s["name"] == child):
            assert any(p["t0"] <= c["t0"] and c["t0"] + c["dur"]
                       <= p["t0"] + p["dur"] + 1e-9 for p in ps), child
        return True

    for child in ("cohort.pad_sizes", "cohort.scenario_plan",
                  "cohort.init_state"):
        within(child, "cohort.engine_init")
    within("cohort.dp_accounting", "cohort.report")
    names = {s["name"] for s in spans}
    assert {"cohort.compile", "cohort.dispatch", "cohort.sync",
            "cohort.eval", "cohort.first_segment", "cohort.steady",
            "cohort.report"} <= names
    assert isinstance(sim.engine.timer, SpanRecorder)
