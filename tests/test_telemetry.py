"""Telemetry subsystem: the cross-engine counter parity contract, DP
accounting rows, JSONL traces, and profiling (repro.telemetry).

The contract under test (ISSUE 6 acceptance): integer telemetry
counters — per-client participation, bytes-on-wire, the
staleness-at-apply histogram, and the overflow high-water mark — are
bitwise identical between the host and device cohort engines, and
exactly equal to the event simulator's ground truth at d = 1 under
deterministic-compatible scenarios (at d > 1 the event sim applies
updates message-by-message while the cohort engines merge each tick's
arrivals before the cascade, so only the cohort pair is pinned there).
"""
import io
import json
import math

import numpy as np
import pytest

from repro.cohort import CohortSimulator, DeviceCohortSimulator
from repro.core import AsyncFLSimulator, LogRegTask
from repro.data import make_binary_dataset
from repro.dp import moments_epsilon, per_client_accounting
from repro.dp.accountant import keys_accounting, participation_keys
from repro.scenarios import LatencyTable, Scenario
from repro.telemetry import (HEADER_BYTES, OP_NAMES, STALE_BINS,
                             JsonlTraceWriter, MetricsReport,
                             SpanRecorder, build_report, check_ops,
                             cost_decomposition, model_flat_dim,
                             participation_sizes, staleness_bin,
                             trace_to_perfetto, update_msg_bytes,
                             validate_trace_events, write_perfetto)


def _task(n=300, d=12, seed=9, sample_seed=21, **kw):
    X, y = make_binary_dataset(n, d, seed=seed, noise=0.3)
    return LogRegTask(X, y, l2=1.0 / n, sample_seed=sample_seed, **kw)


def _counters(report: MetricsReport):
    return dict(messages=report.messages, broadcasts=report.broadcasts,
                participation=list(report.participation),
                bytes_up=list(report.bytes_up),
                bytes_down=list(report.bytes_down),
                staleness_hist=list(report.staleness_hist),
                overflow_hwm=report.overflow_hwm,
                far_messages=report.far_messages)


# --- wire model -------------------------------------------------------------

def test_wire_model_is_engine_invariant():
    task = _task()
    kw = dict(n_clients=4, sizes_per_client=[4, 6],
              round_stepsizes=[0.1, 0.08], d=1, seed=0)
    r_ev = AsyncFLSimulator(task, scenario="uniform", **kw).run(max_rounds=2)
    r_dv = DeviceCohortSimulator(task, scenario="uniform", block=4,
                                 **kw).run(max_rounds=2)
    t_ev, t_dv = r_ev["telemetry"], r_dv["telemetry"]
    # event sim counts pytree scalars, cohort engines use ctask.D == d+1
    assert t_ev.flat_dim == t_dv.flat_dim == 13
    assert t_ev.update_msg_bytes == update_msg_bytes(13) \
        == HEADER_BYTES + 4 * 13
    # per-message byte identity: bytes_up == participation * msg_bytes
    for t in (t_ev, t_dv):
        np.testing.assert_array_equal(
            t.bytes_up, t.participation * t.update_msg_bytes)
        np.testing.assert_array_equal(
            t.bytes_down,
            np.full(t.clients, t.broadcasts * t.broadcast_msg_bytes))


# --- counter parity: event-sim ground truth at d = 1 ------------------------

@pytest.mark.parametrize("preset", ["uniform", "mobile_diurnal"])
def test_counters_match_event_ground_truth(preset):
    """Staleness histogram + bytes-on-wire exactly equal the event sim's
    on presets with a continuous-time form, at the d = 1 hard gate."""
    task = _task()
    kw = dict(n_clients=6, sizes_per_client=[4, 6, 8],
              round_stepsizes=[0.1, 0.08, 0.06], d=1, seed=2)
    r_ev = AsyncFLSimulator(task, scenario=preset, **kw).run(max_rounds=3)
    r_co = CohortSimulator(task, scenario=preset, block=4,
                           **kw).run(max_rounds=3)
    r_dv = DeviceCohortSimulator(task, scenario=preset, block=4,
                                 **kw).run(max_rounds=3)
    want = _counters(r_ev["telemetry"])
    assert _counters(r_co["telemetry"]) == want
    assert _counters(r_dv["telemetry"]) == want
    # d = 1 wait gate: every update applies at zero staleness
    assert want["staleness_hist"][0] == want["messages"] != 0
    assert sum(want["staleness_hist"][1:]) == 0


def test_counters_bitwise_host_vs_device_geo_regional():
    """Host-cohort vs device bitwise on geo_regional (epoch-hash churn —
    no event-sim form) at d = 3 with DP: staleness spreads past bin 0
    and the histograms still agree exactly."""
    task = _task(dp_clip=1.0, dp_sigma=1.5)
    kw = dict(n_clients=8, sizes_per_client=[4, 6, 8],
              round_stepsizes=[0.1, 0.08, 0.06], d=3, seed=5,
              block=4, scenario="geo_regional")
    r_co = CohortSimulator(task, **kw).run(max_rounds=4)
    r_dv = DeviceCohortSimulator(task, **kw).run(max_rounds=4)
    co, dv = _counters(r_co["telemetry"]), _counters(r_dv["telemetry"])
    assert co == dv
    # the d = 3 gate admits staleness >= 1; this seed realizes it, so
    # the test is sensitive to a broken histogram, not vacuous
    assert sum(co["staleness_hist"][1:]) > 0
    # trajectory parity still holds alongside the counters
    assert r_co["final"]["loss"] == r_dv["final"]["loss"]
    # the op census joins the bitwise contract (PR 9)
    assert r_co["telemetry"].ops == r_dv["telemetry"].ops


def test_overflow_hwm_parity_and_run_results():
    """Heavy-tail + small ring_cap routes updates through the far tier:
    the overflow high-water mark and far-message census agree bitwise
    host-vs-device and surface in run() results for ring_cap tuning."""
    task = _task(dp_clip=0.1, dp_sigma=2.0)
    scn = Scenario("tail", LatencyTable.from_uniform(1.0, 200.0, 16),
                   ring_cap=8)
    kw = dict(n_clients=6, sizes_per_client=[4, 6], d=2, seed=2,
              round_stepsizes=[0.1, 0.08], block=4, dp_round_clip=0.5,
              scenario=scn)
    dv = DeviceCohortSimulator(task, **kw)
    assert dv.engine.F > 0                     # far tier active
    r_co = CohortSimulator(task, **kw).run(max_rounds=3)
    r_dv = dv.run(max_rounds=3)
    co, dvc = _counters(r_co["telemetry"]), _counters(r_dv["telemetry"])
    assert co == dvc
    assert dvc["far_messages"] > 0
    assert dvc["overflow_hwm"] > 0
    # surfaced in run() results (ROADMAP carry-over): hwm vs capacity
    assert r_dv["final"]["overflow_hwm"] == dvc["overflow_hwm"]
    assert r_dv["final"]["far_messages"] == dvc["far_messages"]
    assert 0 < r_dv["final"]["overflow_hwm"] \
        <= r_dv["final"]["overflow_slots"] == dv.engine.Q
    assert r_co["final"]["overflow_hwm"] == dvc["overflow_hwm"]
    # far-tier op-census counters agree bitwise and actually fired
    ops = r_dv["telemetry"].ops
    assert r_co["telemetry"].ops == ops
    assert ops["far_groups"] > 0 and ops["far_ticks"] > 0


# --- staleness histogram semantics ------------------------------------------

def test_staleness_bin_clamps_to_last():
    assert staleness_bin(0) == 0
    assert staleness_bin(STALE_BINS - 2) == STALE_BINS - 2
    assert staleness_bin(STALE_BINS - 1) == STALE_BINS - 1
    assert staleness_bin(STALE_BINS + 40) == STALE_BINS - 1


def test_staleness_bounded_by_gate():
    """The wait gate bounds staleness-at-apply by d - 1 on every engine."""
    task = _task()
    d = 3
    kw = dict(n_clients=4, sizes_per_client=[2, 3],
              round_stepsizes=[0.1, 0.08], d=d, seed=1, block=4,
              scenario="uniform")
    r = DeviceCohortSimulator(task, **kw).run(max_rounds=4)
    hist = r["telemetry"].staleness_hist
    assert hist[:d].sum() == hist.sum() != 0


# --- DP accounting ----------------------------------------------------------

def test_per_client_accounting_rows():
    rows = per_client_accounting([[4, 6, 8], [4, 6], [], [4, 6, 8]],
                                 N_c=300, sigma=2.0, delta=1e-5)
    assert [r["client"] for r in rows] == [0, 1, 2, 3]
    assert [r["rounds_contributed"] for r in rows] == [3, 2, 0, 3]
    assert rows[2]["epsilon"] == 0.0           # never participated
    # identical schedules share one bisection -> identical epsilon
    assert rows[0]["epsilon"] == rows[3]["epsilon"]
    # fewer rounds cannot cost more privacy
    assert rows[1]["epsilon"] <= rows[0]["epsilon"]
    # rows agree with a direct accountant call
    want = moments_epsilon([4, 6, 8], 300, 2.0, 1e-5)
    assert rows[0]["epsilon"] == pytest.approx(want)


def test_per_client_accounting_inf_is_none():
    rows = per_client_accounting([[64]], N_c=100, sigma=0.3, delta=1e-9)
    assert rows[0]["epsilon"] is None          # below Lemma 4's regime


def test_participation_sizes_prefix_rule():
    rows = participation_sizes([[4, 6, 8], [5]], [5, 2])
    assert rows[0] == [4, 6, 8, 8, 8]          # last size repeats
    assert rows[1] == [5, 5]


def test_participation_sizes_from_engine_table():
    # the cohort engines hold their schedules as a dense [C, L] table
    table = np.array([[4, 6, 8], [4, 6, 8], [5, 5, 5]], np.int64)
    rows = participation_sizes(table, np.array([5, 0, 2]))
    assert rows == [[4, 6, 8, 8, 8], [], [5, 5]]


# Fig. 1b's schedule s_i = 16 + ceil(1.322 i) (Example 3), cut to 25
# rounds as in the benchmark, and at the paper's own 183 rounds
_FIG1B = [16 + math.ceil(1.322 * i) for i in range(183)]


def _scalar_epsilon(row, N_c, sigma, delta):
    """The accounting rows' epsilon by the scalar accountant, one row."""
    if not row:
        return 0.0
    if sigma <= 0:
        return None
    try:
        eps = moments_epsilon(list(row), N_c, sigma, delta)
    except ValueError:
        return None
    return None if math.isinf(eps) else eps


@pytest.mark.parametrize("rows,N_c,sigma,delta", [
    pytest.param([_FIG1B[:n] for n in range(26)]
                 + [_FIG1B[:25] + [_FIG1B[24]] * k for k in (1, 7)],
                 60_000, 8.0, 1e-5, id="fig1b_prefixes"),
    pytest.param([_FIG1B[:n] for n in (1, 64, 120, 183)], 60_000, 8.0,
                 1e-5, id="paper_183_rounds"),
    pytest.param([[4, 6, 8], [4, 6], [9, 3, 7, 7], [5], [4, 6, 8],
                  [30, 2, 2, 2, 2, 2]], 300, 2.0, 1e-5, id="ragged"),
    pytest.param([[64], [8, 8]], 100, 0.3, 1e-9, id="below_regime"),
    pytest.param([[4, 6], [], [4]], 300, 0.0, 1e-5, id="sigma_zero"),
    pytest.param([[], [], [12, 12]], 300, 2.0, 1e-5, id="empty_rows"),
    # N_c / (s sigma) < e: the lambda cap sigma^2 ln(max(., e)) = 9 binds
    pytest.param([[400, 600], [30], [30, 600, 30]], 1000, 3.0, 1e-5,
                 id="lambda_cap_binds"),
])
def test_per_client_accounting_equals_scalar_oracle(rows, N_c, sigma,
                                                    delta):
    """Every epsilon the vectorised accountant reports is, bit for bit,
    moments_epsilon of the client's own row."""
    got = per_client_accounting(rows, N_c, sigma, delta)
    assert [r["epsilon"] for r in got] == [
        _scalar_epsilon(r, N_c, sigma, delta) for r in rows]
    assert [r["samples"] for r in got] == [sum(r) for r in rows]
    assert [r["rounds_contributed"] for r in got] == [len(r) for r in rows]


def test_per_client_accounting_explicit_r0():
    rows = [[4, 6, 8], [4, 6], [4, 6, 8]]
    got = per_client_accounting(rows, 300, 4.0, 1e-5, r0=1 / math.e)
    want = [moments_epsilon(r, 300, 4.0, 1e-5, r0=1 / math.e) for r in rows]
    assert [r["epsilon"] for r in got] == want
    # r0 = 1/e is looser than the default, max s / N_c * sigma
    default = per_client_accounting(rows, 300, 4.0, 1e-5)
    assert all(g["epsilon"] > d["epsilon"] for g, d in zip(got, default))


@pytest.mark.parametrize("schedules,counts,N_c,sigma", [
    pytest.param(np.array([_FIG1B[:25]] * 34), list(range(34)), 60_000,
                 8.0, id="fig1b_table"),
    # sizes past a client's count must not move its r0 or its lambda cap
    pytest.param([[30, 30, 600], [30, 30, 600], [30, 30, 600], [30],
                  [600, 30]], [1, 2, 5, 3, 2], 1000, 3.0,
                 id="ragged_cap_past_count"),
    pytest.param(np.array([[4, 6, 8], [8, 6, 4], [4, 6, 8]]), [3, 3, 0],
                 300, 2.0, id="table_rows_differ"),
])
def test_keys_accounting_equals_scalar_oracle(schedules, counts, N_c,
                                              sigma):
    """The report's path, (schedule, count) keys, against the scalar
    accountant over the sizes each client sent (the last size of a
    schedule repeats past its end)."""
    rows = [[int(s[min(i, len(s) - 1)]) for i in range(n)]
            for s, n in zip(schedules, counts)]
    got = keys_accounting(*participation_keys(schedules, counts), N_c,
                          sigma, 1e-5)
    assert [r["epsilon"] for r in got] == [
        _scalar_epsilon(r, N_c, sigma, 1e-5) for r in rows]
    assert [r["samples"] for r in got] == [sum(r) for r in rows]


def test_dp_rows_in_engine_reports():
    task = _task(dp_clip=1.0, dp_sigma=2.0)
    kw = dict(n_clients=4, sizes_per_client=[4, 6],
              round_stepsizes=[0.1, 0.08], d=1, seed=0, block=4,
              scenario="uniform")
    r_co = CohortSimulator(task, **kw).run(max_rounds=2)
    r_dv = DeviceCohortSimulator(task, **kw).run(max_rounds=2)
    for r in (r_co, r_dv):
        t = r["telemetry"]
        assert t.dp is not None and len(t.dp) == 4
        for row, did in zip(t.dp, t.participation):
            assert row["rounds_contributed"] == int(did)
            assert row["sigma"] == 2.0
            assert row["epsilon"] is not None and row["epsilon"] > 0
    # same participation => same accounting on both engines
    assert r_co["telemetry"].dp == r_dv["telemetry"].dp
    # no-DP runs carry no accounting rows
    r_plain = DeviceCohortSimulator(_task(), **kw).run(max_rounds=2)
    assert r_plain["telemetry"].dp is None


@pytest.mark.parametrize("ragged", [False, True], ids=["shared", "ragged"])
def test_dp_accounting_span_counts_keys(ragged):
    """``cohort.dp_accounting`` nests in ``cohort.report`` and carries
    the distinct keys the accountant evaluated and the clients charged:
    on one schedule a key per distinct participation count, on per-client
    schedules a key per distinct (schedule row, count)."""
    C = 12
    sizes = [[4, 6, 8], [5], [4, 6]] * 4 if ragged else [4, 6, 8]
    sim = DeviceCohortSimulator(
        _task(dp_clip=1.0, dp_sigma=2.0), n_clients=C,
        sizes_per_client=sizes, round_stepsizes=[0.1, 0.08, 0.06], d=2,
        seed=3, block=4, scenario="mobile_diurnal")
    rep = sim.run(max_rounds=6)["telemetry"]
    spans = sim.engine.timer.spans
    (acc,) = [s for s in spans if s["name"] == "cohort.dp_accounting"]
    (report,) = [s for s in spans if s["name"] == "cohort.report"]
    assert report["t0"] <= acc["t0"]
    assert acc["t0"] + acc["dur"] <= report["t0"] + report["dur"] + 1e-9
    part = [int(p) for p in rep.participation]
    if ragged:
        keys = {(tuple(sim.engine.sizes[c]), part[c]) for c in range(C)}
        assert len(keys) > len({tuple(r) for r in sim.engine.sizes})
    else:
        keys = set(part)
    assert len(keys) > 1                # the scenario spreads the counts
    assert acc["args"] == {"keys": len(keys), "clients": C}


# --- JSONL traces -----------------------------------------------------------

def test_event_trace_jsonl_roundtrip():
    task = _task()
    buf = io.StringIO()
    kw = dict(n_clients=4, sizes_per_client=[4, 6],
              round_stepsizes=[0.1, 0.08], d=1, seed=0)
    res = AsyncFLSimulator(task, scenario="uniform", trace=buf,
                           **kw).run(max_rounds=2)
    recs = [json.loads(line) for line in
            buf.getvalue().strip().splitlines()]
    kinds = {r["kind"] for r in recs}
    assert {"update_sent", "update_applied", "broadcast_fired",
            "broadcast_applied", "report"} <= kinds
    t = res["telemetry"]
    sent = [r for r in recs if r["kind"] == "update_sent"]
    assert len(sent) == t.messages
    assert all(r["bytes"] == t.update_msg_bytes for r in sent)
    applied = [r for r in recs if r["kind"] == "update_applied"]
    # trace staleness values reproduce the histogram
    hist = np.zeros(STALE_BINS, dtype=np.int64)
    for r in applied:
        hist[staleness_bin(r["staleness"])] += 1
    np.testing.assert_array_equal(hist, t.staleness_hist)
    fired = [r for r in recs if r["kind"] == "broadcast_fired"]
    assert len(fired) == t.broadcasts
    # the final record is the full report
    rep = [r for r in recs if r["kind"] == "report"]
    assert len(rep) == 1 and rep[0]["messages"] == t.messages


@pytest.mark.parametrize("engine", ["cohort", "device"])
def test_cohort_segment_trace(engine, tmp_path):
    task = _task()
    path = tmp_path / f"{engine}.jsonl"
    cls = CohortSimulator if engine == "cohort" else DeviceCohortSimulator
    res = cls(task, n_clients=4, sizes_per_client=[4, 6],
              round_stepsizes=[0.1, 0.08], d=1, seed=0, block=4,
              scenario="uniform", trace=str(path)).run(max_rounds=3,
                                                       eval_every=1)
    recs = [json.loads(line) for line in
            path.read_text().strip().splitlines()]
    segs = [r for r in recs if r["kind"] == "segment"]
    assert len(segs) == len(res["history"])
    assert [s["round"] for s in segs] == \
        [h["round"] for h in res["history"]]
    for s in segs:
        assert s["messages"] >= 0 and len(s["staleness_hist"]) == STALE_BINS
    rep = [r for r in recs if r["kind"] == "report"]
    assert len(rep) == 1
    assert rep[0]["messages"] == res["telemetry"].messages
    assert rep[0]["participation"] == \
        [int(x) for x in res["telemetry"].participation]


# --- report schema / serialization ------------------------------------------

def test_report_to_json_roundtrip():
    rep = build_report(
        engine="host", clients=3, flat_dim=10, rounds=2, messages=6,
        broadcasts=2, participation=np.array([2, 2, 2]),
        bytes_up=np.array([112, 112, 112]),
        staleness_hist=np.zeros(STALE_BINS, np.int64),
        wall={"run": 0.5})
    d = json.loads(rep.to_json())
    assert d["engine"] == "host" and d["clients"] == 3
    assert d["bytes_down"] == [2 * rep.broadcast_msg_bytes] * 3
    assert isinstance(rep.summary(), str) and "rounds=2" in rep.summary()


def test_model_flat_dim_counts_pytree_scalars():
    assert model_flat_dim({"w": np.zeros((3, 4)), "b": np.zeros(())}) == 13


def test_span_recorder_accumulates():
    t = SpanRecorder()
    with t.phase("a"):
        pass
    with t.phase("a"):
        pass
    with t.phase("b"):
        pass
    assert t.counts["a"] == 2 and t.counts["b"] == 1
    d = t.as_dict()
    # seconds per span name plus span counts
    assert set(d) == {"a_s", "b_s", "a_n", "b_n"}
    assert all(v >= 0 for v in d.values())
    assert d["a_n"] == 2 and d["b_n"] == 1
    # from a later span on: only what was recorded since
    assert t.as_dict(since=2) == {"b_s": t.spans[2]["dur"], "b_n": 1}


def test_engine_reports_carry_wall_phases():
    task = _task()
    kw = dict(n_clients=4, sizes_per_client=[4, 6],
              round_stepsizes=[0.1, 0.08], d=1, seed=0)
    r_dv = DeviceCohortSimulator(task, block=4, scenario="uniform",
                                 **kw).run(max_rounds=2)
    wall = r_dv["telemetry"].wall
    assert "first_segment_s" in wall
    # construction, the compile of the segment, each segment's dispatch
    # and sync, and the segment-cache misses
    for k in ("engine_init", "pad_sizes", "scenario_plan", "init_state",
              "compile", "dispatch", "sync", "eval"):
        assert wall[f"{k}_s"] >= 0 and wall[f"{k}_n"] >= 1, k
    assert wall["compiles"] == 1 and wall["dispatch_n"] == wall["sync_n"]
    r_ev = AsyncFLSimulator(task, scenario="uniform", **kw).run(max_rounds=2)
    assert r_ev["telemetry"].wall["run_s"] > 0


def test_trace_writer_coerces_numpy():
    buf = io.StringIO()
    w = JsonlTraceWriter(buf)
    w.emit("x", a=np.int64(3), b=np.arange(2), c=np.float32(0.5))
    w.close()
    assert json.loads(buf.getvalue()) == \
        {"kind": "x", "a": 3, "b": [0, 1], "c": 0.5}


# --- op census (PR 9) --------------------------------------------------------

@pytest.mark.parametrize("preset,strategy", [
    ("uniform", None),
    ("mobile_diurnal", "fedasync"),
    ("iot_straggler", "fedbuff"),
])
def test_op_census_bitwise_host_vs_device(preset, strategy):
    """The op-census vector joins the bitwise parity contract on DP +
    stochastic presets and every aggregation strategy."""
    task = _task(dp_clip=1.0, dp_sigma=1.5)
    kw = dict(n_clients=6, sizes_per_client=[4, 6, 8],
              round_stepsizes=[0.1, 0.08, 0.06], d=2, seed=3, block=4,
              scenario=preset, strategy=strategy)
    r_co = CohortSimulator(task, **kw).run(max_rounds=3)
    r_dv = DeviceCohortSimulator(task, **kw).run(max_rounds=3)
    co, dv = r_co["telemetry"].ops, r_dv["telemetry"].ops
    assert co == dv
    assert tuple(co) == OP_NAMES
    assert co["ticks"] == r_co["telemetry"].ticks > 0
    assert co["block_ticks"] > 0 and co["complete_ticks"] > 0
    # float trajectory is unperturbed by the counter threading
    assert r_co["final"]["loss"] == r_dv["final"]["loss"]
    # the check_ops relations hold on a real run, on both engines
    for rep in (r_co["telemetry"], r_dv["telemetry"]):
        assert check_ops(rep.ops, messages=rep.messages,
                         broadcasts=rep.broadcasts,
                         far_messages=rep.far_messages,
                         clients=rep.clients, ticks=rep.ticks) == []


def test_check_ops_flags_inconsistencies():
    ops = dict.fromkeys(OP_NAMES, 0)
    ops.update(ticks=10, block_ticks=11)            # gated > ticks
    assert any("block_ticks" in p for p in check_ops(ops))
    ops = dict.fromkeys(OP_NAMES, 0)
    ops.update(ticks=10, complete_ticks=5)
    assert any("complete_ticks" in p
               for p in check_ops(ops, messages=3))
    ops = dict.fromkeys(OP_NAMES, 0)
    ops.update(ticks=10, far_ticks=4, far_groups=2)
    assert any("far_ticks" in p
               for p in check_ops(ops, far_messages=9))


def test_cost_decomposition_roofline_ratio():
    ops = dict.fromkeys(OP_NAMES, 0)
    ops.update(ticks=20, block_ticks=5, ring_scatters=8)
    dec = cost_decomposition(ops, steady_s=2.0)
    assert dec["tick_overhead_ratio"] == pytest.approx(0.75)
    assert dec["ring_scatters_per_tick"] == pytest.approx(0.4)
    assert dec["s_per_tick"] == pytest.approx(0.1)
    assert cost_decomposition({"ticks": 0}) == {}


# --- span recorder + Perfetto export (PR 9) ---------------------------------

def test_span_recorder_tracks_and_trace_events():
    rec = SpanRecorder()
    with rec.phase("steady", seg=1):
        pass
    with rec.phase("steady", seg=2):
        pass
    rec.add("compile", 0.25)
    events = rec.to_trace_events()
    doc = {"traceEvents": events}
    assert validate_trace_events(doc) == []
    slices = [e for e in events if e["ph"] == "X"]
    assert len(slices) == 3
    assert {e["name"] for e in slices} == {"steady", "compile"}
    # re-entrant phases stay on one track, back to back, not stacked
    assert len({(e["pid"], e["tid"]) for e in slices
                if e["name"] == "steady"}) == 1


def test_perfetto_event_trace_has_flows(tmp_path):
    """Event-sim JSONL -> Perfetto: message lifecycles become flow
    events on virtual-protocol time and the doc validates + round-trips
    through json.load."""
    task = _task()
    buf = io.StringIO()
    res = AsyncFLSimulator(task, n_clients=4, sizes_per_client=[4, 6],
                           round_stepsizes=[0.1, 0.08], d=1, seed=0,
                           scenario="uniform", trace=buf).run(max_rounds=2)
    records = [json.loads(line) for line in
               buf.getvalue().strip().splitlines()]
    events = trace_to_perfetto(records)
    out = tmp_path / "trace.json"
    write_perfetto(str(out), events)
    with open(out) as fh:
        doc = json.load(fh)
    assert validate_trace_events(doc) == []
    phs = {e["ph"] for e in doc["traceEvents"]}
    assert {"s", "f", "i", "M"} <= phs          # flows + instants
    flows = [e for e in doc["traceEvents"] if e["ph"] in ("s", "f")]
    assert len(flows) >= 2 * res["telemetry"].messages


def test_perfetto_device_trace_segments(tmp_path):
    """Device-engine JSONL (segment summaries) -> Perfetto slices on
    the virtual clock, plus the run's wall spans, in one document."""
    task = _task()
    buf = io.StringIO()
    sim = DeviceCohortSimulator(task, n_clients=4, sizes_per_client=[4, 6],
                                round_stepsizes=[0.1, 0.08], d=1, seed=0,
                                block=4, scenario="uniform", trace=buf)
    sim.run(max_rounds=3, eval_every=1)
    records = [json.loads(line) for line in
               buf.getvalue().strip().splitlines()]
    events = trace_to_perfetto(records)
    events += sim.engine.timer.to_trace_events(process="wall")
    # two processes may share builder-less ids; validate separately
    assert validate_trace_events({"traceEvents": events},
                                 check_overlap=False) == []
    seg_slices = [e for e in events
                  if e["ph"] == "X" and e.get("args", {}).get("ops")]
    assert seg_slices, "segment slices should carry op-census args"


def test_write_perfetto_rejects_malformed(tmp_path):
    bad = [{"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0}]
    with pytest.raises(ValueError):
        write_perfetto(str(tmp_path / "bad.json"), bad)


def test_telemetry_cli_capture_and_convert(tmp_path):
    """ONE CLI invocation produces a Perfetto-loadable trace JSON."""
    from repro.telemetry.__main__ import main
    out = tmp_path / "timeline.json"
    jl = tmp_path / "run.jsonl"
    rc = main(["capture", "--engine", "event", "--rounds", "2",
               "--clients", "4", "--out", str(out),
               "--jsonl-out", str(jl)])
    assert rc == 0
    with open(out) as fh:
        doc = json.load(fh)
    assert doc["traceEvents"] and validate_trace_events(doc) == []
    out2 = tmp_path / "converted.json"
    assert main(["convert", str(jl), "--out", str(out2)]) == 0
    with open(out2) as fh:
        doc2 = json.load(fh)
    assert validate_trace_events(doc2) == []
