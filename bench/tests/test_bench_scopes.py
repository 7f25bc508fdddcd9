"""The program's names in a trace (``yardstick/scopes.py``,
``scope_profile.py``): device time per scope of the compiled segment,
the program's host spans, and idle gaps under the innermost span.

Hand-computed intervals; a CPU profiler capture of a small device-engine
job; and a small trace recorded on a TPU v5e with the program's spans
and the segment's scope table (one FedSGD harness job at C = 4096, 3
rounds, the host threads' events other than the ``bench.`` and
``cohort.`` spans dropped to keep it small; the device plane is as
recorded)."""
import glob
import json
import os

import pytest

import scope_profile
from yardstick import scopes, trace

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def test_scope_times_by_hand():
    table = {"fusion.1": "cohort.segment/cohort.client_block",
             "pad.2": "cohort.segment/cohort.deliver/tick_deliver",
             "tick_deliver.3": "cohort.segment/cohort.deliver/tick_deliver",
             "all-reduce.4": ("cohort.segment/cohort.complete/tick_scatter/"
                              "cohort.allreduce"),
             "fusion.9": "cohort.segment/cohort.complete"}
    devices = {
        0: [("%fusion.1 = f32[8] fusion(...)", 0, 10),
            ("%pad.2 = f32[8] pad(...)", 10, 20),
            ("%tick_deliver.3 = f32[8] custom-call(...)", 20, 40),
            ("%copy.5 = f32[8] copy(...)", 40, 50),          # no scope
            ("%fusion.7 = f32[8] fusion(...)", 100, 130)],   # the eval
        1: [("%fusion.1 = f32[8] fusion(...)", 0, 30),
            ("%all-reduce.4 = f32[8] all-reduce(...)", 30, 40)]}
    programs = {0: [("jit_segment(1)", 0, 60), ("jit_metrics(2)", 90, 140)],
                1: [("jit_segment(1)", 0, 60)]}
    r = scopes.scope_times(devices, (0, 200), table, programs)
    ns = 1e-9 / 2                                 # a mean over two chips
    assert r["segment_s"] == pytest.approx(90 * ns)
    assert r["other_programs_s"] == pytest.approx(30 * ns)
    assert r["unscoped_s"] == pytest.approx(10 * ns)
    assert r["unscoped_ops_s"] == {"copy": pytest.approx(10 * ns)}
    assert r["client_block_s"] == pytest.approx(40 * ns)
    # the wrapper's pad, not the kernel, and not the all-reduce
    assert r["kernel_layout_s"] == pytest.approx(10 * ns)
    assert r["collective_s"] == pytest.approx(10 * ns)
    assert r["per_scope_s"]["cohort.segment/cohort.deliver/tick_deliver"] \
        == pytest.approx(30 * ns)
    assert r["per_chip_s"][1] == {
        "cohort.segment/cohort.client_block": pytest.approx(30e-9),
        table["all-reduce.4"]: pytest.approx(10e-9)}
    # without the programs every leaf op counts as the segment's
    r = scopes.scope_times(devices, (0, 200), table)
    assert r["segment_s"] == pytest.approx(120 * ns)
    assert scopes.top({"a": 1.0, "": 3.0, "b": 2.0}, 2) == [
        ["(unscoped)", 3.0], ["b", 2.0]]


def test_program_metrics_by_hand():
    spans = [("cohort.engine_init", 0, 9e9),          # outside the window
             ("cohort.engine_init", 10e9, 11e9),
             ("cohort.report", 20e9, 22e9),
             ("cohort.engine_init", 30e9, 33e9),
             ("cohort.report", 40e9, 46e9),
             ("bench.job_run", 12e9, 23e9)]
    out = scope_profile.program_metrics(spans, (10e9, 50e9),
                                        {"compile_s": 50.0})
    assert out == {"engine_init_ms": pytest.approx(2e3),
                   "report_ms_per_job": pytest.approx(4e3),
                   "segment_compile_s": 50.0}
    # a compile inside the window adds to the warm-up's; no span, no
    # metric
    out = scope_profile.program_metrics(
        spans + [("cohort.compile", 47e9, 48e9)], (10e9, 50e9), {})
    assert out["segment_compile_s"] == pytest.approx(1.0)
    assert scope_profile.program_metrics([], (0, 1), {}) == {}


def _within(spans, child, *parents):
    ps = [(s, e) for n, s, e in spans if n in parents]
    cs = [(s, e) for n, s, e in spans if n == child]
    assert cs and ps, (child, parents)
    return all(any(ps_ <= s and e <= pe for ps_, pe in ps) for s, e in cs)


def test_cpu_capture_holds_nested_program_spans(tmp_path):
    """A profiler capture of a small DP job: the program's spans sit on
    the host planes, nested, on the clock of the harness's own span
    around them; an idle gap is labelled by the innermost span."""
    import jax
    from repro.cohort import DeviceCohortSimulator
    from repro.core import LogRegTask
    from repro.data import make_binary_dataset
    X, y = make_binary_dataset(300, 12, seed=7, noise=0.3)
    task = LogRegTask(X, y, l2=1.0 / 300, sample_seed=21, dp_clip=1.0,
                      dp_sigma=1.5)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.job_run"):
        sim = DeviceCohortSimulator(
            task, n_clients=6, sizes_per_client=[4, 6, 8],
            round_stepsizes=[0.1, 0.08, 0.06], d=2, seed=3, block=4,
            scenario="mobile_diurnal")
        sim.run(max_rounds=2)
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    spans = scopes.program_spans(path)
    names = {n for n, _, _ in spans}
    assert {"cohort.engine_init", "cohort.pad_sizes", "cohort.scenario_plan",
            "cohort.init_state", "cohort.compile", "cohort.first_segment",
            "cohort.steady", "cohort.dispatch", "cohort.sync", "cohort.eval",
            "cohort.report", "cohort.dp_accounting"} <= names
    assert _within(spans, "cohort.pad_sizes", "cohort.engine_init")
    for seg in ("cohort.dispatch", "cohort.sync"):
        assert _within(spans, seg, "cohort.first_segment", "cohort.steady")
    assert _within(spans, "cohort.dp_accounting", "cohort.report")
    _, _, bench = trace.read(path, chips=1)
    assert [n for n, _, _ in bench] == ["bench.job_run"]
    every = bench + spans
    assert all(_within(every, n, "bench.job_run") for n in names)
    # a gap in the middle of the DP accounting: the innermost span
    (s, e), = [(s, e) for n, s, e in spans if n == "cohort.dp_accounting"]
    mid = 0.5 * (s + e)
    assert trace.label_gaps([(mid - 1, mid + 1)], every)[0][0] == (
        "cohort.dp_accounting")
    # the engine's own record of the same spans agrees in count
    own = sim.engine.timer.counts
    assert own["cohort.sync"] == sum(n == "cohort.sync" for n, _, _ in spans)


def _fixture():
    found = glob.glob(os.path.join(FIXTURES, "v5e_scopes_*",
                                   "*.xplane.pb"))
    assert found, f"no scoped trace fixture under {FIXTURES}"
    return found[0]


def test_recorded_tpu_trace_by_scope():
    """The scoped v5e fixture: every device op of the segment joins the
    recorded scope table or is a compiler-inserted op; the readings are
    pinned.  The table was recorded while the scatter's guarded add of
    the ring rows still sat inside the ``tick_scatter`` scope: its
    ``add_select_fusion`` (2 us of the job) counts as layout here."""
    path = _fixture()
    assert os.path.getsize(path) < 1 << 20
    with open(os.path.join(os.path.dirname(path), "scopes.json")) as f:
        table = json.load(f)
    devices, programs, spans = trace.read(path, chips=1)
    spans = spans + scopes.program_spans(path)
    window = trace.window_of(spans, "bench.job_setup", "bench.job_run")
    r = trace.reduce(devices, spans, window, programs)
    sc = scopes.scope_times(devices, window, table, programs)
    assert sc["segment_s"] == pytest.approx(2.079516e-3, rel=1e-9)
    assert sc["other_programs_s"] == pytest.approx(2.141582e-3, rel=1e-9)
    assert sc["client_block_s"] == pytest.approx(0.904699e-3, rel=1e-9)
    assert sc["kernel_layout_s"] == pytest.approx(0.226155e-3, rel=1e-9)
    assert sc["unscoped_s"] == pytest.approx(0.507622e-3, rel=1e-9)
    assert sc["collective_s"] == 0.0
    # the compiler's layout copies are the unscoped time
    assert max(sc["unscoped_ops_s"], key=sc["unscoped_ops_s"].get) == "copy"
    assert [k for k, _ in scopes.top(sc["per_scope_s"], 4)] == [
        "cohort.segment/cohort.client_block", "(unscoped)",
        "cohort.segment/cohort.complete/tick_scatter",
        "cohort.segment/cohort.deliver/tick_deliver"]
    # the segment's and the other programs' leaf ops are all the leaves
    total = sum(r["per_op_s"].values())
    assert sc["segment_s"] + sc["other_programs_s"] == pytest.approx(total)
    # the idle gaps now name the program's spans
    assert [g for g, _ in r["idle_gaps"][:3]] == [
        "cohort.pad_sizes", "cohort.report", "cohort.init_state"]
    metrics = scope_profile.program_metrics(spans, window, {})
    assert metrics == {"engine_init_ms": pytest.approx(31.797417),
                       "report_ms_per_job": pytest.approx(1.874399)}
