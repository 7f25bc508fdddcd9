"""Trace invariant checker (repro.analysis.invariants): real engine
traces must model-check clean, and corrupted JSONL must trip each
invariant family — τ bound, bytes census, round conservation, latch
monotonicity, segment monotonicity.
"""
import io
import json

import pytest

from repro.analysis.invariants import check_report, check_trace, read_trace
from repro.cohort import CohortSimulator, DeviceCohortSimulator
from repro.core import AsyncFLSimulator, LogRegTask
from repro.data import make_binary_dataset
from repro.scenarios import LatencyTable, Scenario


def _task(**kw):
    X, y = make_binary_dataset(200, 10, seed=9, noise=0.3)
    return LogRegTask(X, y, l2=0.005, sample_seed=21, **kw)


def _rules(violations):
    return sorted({v.rule for v in violations})


def _event_trace(d=2, **task_kw):
    buf = io.StringIO()
    AsyncFLSimulator(_task(**task_kw), scenario="uniform", trace=buf,
                     n_clients=5, sizes_per_client=[3, 4],
                     round_stepsizes=[0.1, 0.08], d=d,
                     seed=3).run(max_rounds=3)
    return [json.loads(ln) for ln in buf.getvalue().strip().splitlines()]


def _device_trace(tmp_path, d=3, scenario="geo_regional", **task_kw):
    path = tmp_path / "device.jsonl"
    DeviceCohortSimulator(_task(**task_kw), scenario=scenario,
                          n_clients=6, sizes_per_client=[3, 4, 5],
                          round_stepsizes=[0.1, 0.08, 0.06], d=d, seed=5,
                          block=4, trace=str(path)).run(max_rounds=4,
                                                        eval_every=1)
    return str(path)


# --- clean traces model-check clean -------------------------------------------

def test_event_trace_clean(tmp_path):
    recs = _event_trace(d=2)
    assert check_trace(recs, d=2) == []


def test_event_trace_with_dp_clean():
    recs = _event_trace(d=2, dp_clip=1.0, dp_sigma=1.5)
    assert check_trace(recs, d=2) == []


def test_device_trace_golden_scenario_clean(tmp_path):
    """Golden-trajectory-style device run (churny geo_regional, d=3)."""
    path = _device_trace(tmp_path, d=3)
    assert check_trace(path, d=3) == []


def test_device_trace_dp_heavy_tail_churn_clean(tmp_path):
    """DP + heavy-tail latency + small ring (far tier + overflow HWM
    exercised) — the richest segment trace the engines emit."""
    scn = Scenario("tail", LatencyTable.from_uniform(1.0, 200.0, 16),
                   ring_cap=8)
    path = tmp_path / "tail.jsonl"
    res = DeviceCohortSimulator(
        _task(dp_clip=0.1, dp_sigma=2.0), scenario=scn, n_clients=6,
        sizes_per_client=[3, 4], round_stepsizes=[0.1, 0.08], d=2, seed=2,
        block=4, dp_round_clip=0.5, trace=str(path)).run(max_rounds=3,
                                                         eval_every=1)
    assert res["final"]["overflow_hwm"] > 0    # latch actually moved
    assert check_trace(str(path), d=2) == []


def test_host_cohort_trace_clean(tmp_path):
    path = tmp_path / "host.jsonl"
    CohortSimulator(_task(), scenario="mobile_diurnal", n_clients=5,
                    sizes_per_client=[3, 4], round_stepsizes=[0.1, 0.08],
                    d=2, seed=7, block=4,
                    trace=str(path)).run(max_rounds=3, eval_every=1)
    assert check_trace(str(path), d=2) == []


# --- corrupted JSONL trips each family ----------------------------------------

def test_corrupt_tau_exceeds_gate():
    """An apply recorded past the wait gate (τ > d-1) must fire INV-TAU."""
    recs = _event_trace(d=2)
    applied = [r for r in recs if r["kind"] == "update_applied"]
    applied[0]["staleness"] = 7                # d-1 == 1
    found = check_trace(recs, d=2)
    assert "INV-TAU" in _rules(found)
    assert any("wait-gate" in v.message for v in found)


def test_corrupt_negative_staleness():
    recs = _event_trace(d=2)
    applied = [r for r in recs if r["kind"] == "update_applied"]
    applied[-1]["staleness"] = -1
    assert "INV-TAU" in _rules(check_trace(recs, d=2))


def test_corrupt_bytes_census():
    """Report bytes_up no longer equal to Σ update_sent bytes."""
    recs = _event_trace(d=2)
    report = [r for r in recs if r["kind"] == "report"][0]
    report["bytes_up"] = list(report["bytes_up"])
    report["bytes_up"][0] += 1
    found = check_trace(recs, d=2)
    assert "INV-CENSUS" in _rules(found)


def test_corrupt_lost_apply_breaks_round_conservation():
    """Dropping one update_applied leaves a completed round at C-1
    applies — Algorithm 3's H set can't have filled."""
    recs = _event_trace(d=2)
    drop = next(i for i, r in enumerate(recs)
                if r["kind"] == "update_applied" and r["round"] == 0)
    del recs[drop]
    found = check_trace(recs, d=2)
    assert "INV-ROUND" in _rules(found)


def test_corrupt_time_regression():
    recs = _event_trace(d=2)
    events = [r for r in recs if "time" in r]
    events[-1]["time"] = events[0]["time"] - 1.0
    assert "INV-TIME" in _rules(check_trace(recs, d=2))


def test_corrupt_overflow_latch_regression(tmp_path):
    """The overflow HWM is a latch; a later segment reporting a lower
    mark means the census was rebuilt instead of latched."""
    scn = Scenario("tail", LatencyTable.from_uniform(1.0, 200.0, 16),
                   ring_cap=8)
    path = tmp_path / "tail.jsonl"
    DeviceCohortSimulator(
        _task(dp_clip=0.1, dp_sigma=2.0), scenario=scn, n_clients=6,
        sizes_per_client=[3, 4], round_stepsizes=[0.1, 0.08], d=2, seed=2,
        block=4, dp_round_clip=0.5, trace=str(path)).run(max_rounds=3,
                                                         eval_every=1)
    recs = read_trace(str(path))
    segs = [r for r in recs if r["kind"] == "segment"]
    assert len(segs) >= 2 and segs[-1]["overflow_hwm"] > 0
    segs[-1]["overflow_hwm"] = 0               # regress the latch
    found = check_trace(recs, d=2)
    assert "INV-LATCH" in _rules(found)


def test_corrupt_segment_counter_regression(tmp_path):
    path = _device_trace(tmp_path, d=3)
    recs = read_trace(path)
    segs = [r for r in recs if r["kind"] == "segment"]
    segs[-1]["messages"] = segs[0]["messages"] - 1
    found = check_trace(recs, d=3)
    assert "INV-MONO" in _rules(found)


def test_corrupt_staleness_hist_entrywise_regression(tmp_path):
    path = _device_trace(tmp_path, d=3)
    recs = read_trace(path)
    segs = [r for r in recs if r["kind"] == "segment"]
    # the last segment falls one below its predecessor on bin 0: a
    # regression whatever the draws added to that bin in between
    assert segs[-2]["staleness_hist"][0] > 0
    segs[-1]["staleness_hist"] = list(segs[-1]["staleness_hist"])
    segs[-1]["staleness_hist"][0] = segs[-2]["staleness_hist"][0] - 1
    assert "INV-MONO" in _rules(check_trace(recs, d=3))


# --- report-level checks --------------------------------------------------------

def test_check_report_census_identities():
    rep = {"clients": 2, "messages": 5, "broadcasts": 2,
           "participation": [3, 2], "update_msg_bytes": 10,
           "broadcast_msg_bytes": 8, "bytes_up": [30, 20],
           "bytes_down": [16, 16], "staleness_hist": [5, 0, 0, 0],
           "overflow_hwm": 1, "overflow_slots": 4}
    assert check_report(rep, d=1) == []
    bad = dict(rep, participation=[3, 3])       # Σ != messages
    assert _rules(check_report(bad, d=1)) == ["INV-CENSUS"]
    bad = dict(rep, staleness_hist=[4, 1, 0, 0])  # mass past d-1
    assert _rules(check_report(bad, d=1)) == ["INV-TAU"]
    bad = dict(rep, overflow_hwm=9)              # over capacity
    assert _rules(check_report(bad, d=1)) == ["INV-LATCH"]
    bad = dict(rep, bytes_down=[16, 24])
    assert _rules(check_report(bad, d=1)) == ["INV-CENSUS"]


def test_read_trace_rejects_malformed_lines(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"kind": "report"}\nnot json\n')
    with pytest.raises(ValueError, match="line 2"):
        read_trace(str(p))
    p.write_text('{"no_kind": 1}\n')
    with pytest.raises(ValueError, match="kind"):
        read_trace(str(p))


def test_check_trace_accepts_lines_and_paths(tmp_path):
    recs = _event_trace(d=2)
    lines = [json.dumps(r) for r in recs]
    assert check_trace(lines, d=2) == []        # iterable of JSONL lines
    p = tmp_path / "t.jsonl"
    p.write_text("\n".join(lines) + "\n")
    assert check_trace(str(p), d=2) == []       # path (where=path)


# --- op-census + timeline discipline (INV-SPAN, PR 9) -------------------------

def test_corrupt_segment_ops_regression(tmp_path):
    """Per-segment op-census counters are cumulative; one regressing
    entrywise means an increment site was rebuilt, not accumulated."""
    path = _device_trace(tmp_path, d=3)
    recs = read_trace(path)
    segs = [r for r in recs if r["kind"] == "segment"]
    assert segs[0]["ops"][0] > 0                # ticks counted
    segs[-1]["ops"] = list(segs[-1]["ops"])
    segs[-1]["ops"][0] = segs[0]["ops"][0] - 1  # below an earlier segment
    assert "INV-SPAN" in _rules(check_trace(recs, d=3))


def test_corrupt_report_ops_relations(tmp_path):
    """Report op census inconsistent with the message counts fires
    INV-SPAN (complete_ticks cannot exceed messages)."""
    path = _device_trace(tmp_path, d=3)
    recs = read_trace(path)
    report = [r for r in recs if r["kind"] == "report"][0]
    assert check_trace(recs, d=3) == []         # clean before corruption
    report["ops"] = dict(report["ops"],
                         complete_ticks=report["messages"] + 1)
    found = check_trace(recs, d=3)
    assert "INV-SPAN" in _rules(found)
    assert any("complete_ticks" in v.message for v in found)


def test_check_perfetto_overlap_and_shape(tmp_path):
    from repro.analysis.invariants import check_perfetto
    ok = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0, "dur": 5},
        {"ph": "X", "name": "b", "pid": 1, "tid": 1, "ts": 5, "dur": 3},
    ]}
    assert check_perfetto(ok) == []
    overlapping = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0, "dur": 5},
        {"ph": "X", "name": "b", "pid": 1, "tid": 1, "ts": 3, "dur": 3},
    ]}
    found = check_perfetto(overlapping)
    assert _rules(found) == ["INV-SPAN"]
    missing_dur = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0}]}
    assert _rules(check_perfetto(missing_dur)) == ["INV-SPAN"]
    # path form: a real exported document checks clean
    import json as _json
    from repro.telemetry import trace_to_perfetto, write_perfetto
    recs = _event_trace(d=2)
    out = tmp_path / "trace.json"
    write_perfetto(str(out), trace_to_perfetto(recs))
    assert check_perfetto(str(out)) == []
