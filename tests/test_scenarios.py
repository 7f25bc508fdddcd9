"""Scenario subsystem: latency tables + alias sampling, availability
models, the preset registry, and the unified spec across all three
engines (repro.scenarios)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cohort import CohortSimulator, DeviceCohortSimulator
from repro.core import AsyncFLSimulator, LogRegTask
from repro.data import make_binary_dataset
from repro.scenarios import (AlwaysOn, Churn, Diurnal, LatencyTable,
                             RegionalChurn, RenewalChurn, Scenario,
                             SpeedModel, TableAssignment, alias_sample,
                             get_scenario, implied_probs, key_uniforms,
                             scenario_from_trace, scenario_names,
                             scenario_plan)


def _task(n=300, d=12, seed=9, sample_seed=21, **kw):
    X, y = make_binary_dataset(n, d, seed=seed, noise=0.3)
    return LogRegTask(X, y, l2=1.0 / n, sample_seed=sample_seed, **kw)


# --- LatencyTable -----------------------------------------------------------

def test_table_validation():
    with pytest.raises(ValueError):
        LatencyTable((), ())
    with pytest.raises(ValueError):
        LatencyTable((1.0, 0.5), (0.5, 0.5))         # not ascending
    with pytest.raises(ValueError):
        LatencyTable((-1.0,), (1.0,))                # non-positive value
    with pytest.raises(ValueError):
        LatencyTable((1.0,), (-1.0,))                # negative prob
    t = LatencyTable((1.0, 2.0), (3.0, 1.0))         # normalizes
    assert t.probs == (0.75, 0.25)


def test_table_constructors_are_distributions():
    tables = [
        LatencyTable.constant(0.5),
        LatencyTable.from_uniform(0.05, 0.1, 8),
        LatencyTable.from_samples([0.1, 0.2, 0.2, 0.9, 1.4], n_bins=4),
        LatencyTable.from_lognormal(0.3, 0.8, 12),
        LatencyTable.from_pareto(0.1, 1.2, 12, q_hi=0.99),
        LatencyTable.mix([LatencyTable.constant(0.1),
                          LatencyTable.constant(1.0)], [0.7, 0.3]),
    ]
    for t in tables:
        assert abs(sum(t.probs) - 1.0) < 1e-12
        assert all(b >= a for a, b in zip(t.values, t.values[1:]))
        assert all(v > 0 for v in t.values)
        # alias decomposition encodes exactly the bin probabilities
        np.testing.assert_allclose(implied_probs(*t.alias_arrays()),
                                   np.asarray(t.probs), atol=1e-7)


def test_table_json_roundtrip_exact():
    t = LatencyTable.from_lognormal(0.3, 0.8, 12)
    assert LatencyTable.from_json(t.to_json()) == t


def test_table_tick_quantization_matches_legacy_rule():
    t = LatencyTable((0.5, 4.0, 4.0001, 9.9), (0.25,) * 4)
    np.testing.assert_array_equal(t.tick_values(dt=4.0), [1, 1, 2, 3])
    assert LatencyTable.constant(5.0).tick_values(dt=4.0) == [2]


def test_table_stats():
    t = LatencyTable((1.0, 3.0), (0.5, 0.5))
    assert t.mean() == 2.0
    assert t.quantile(0.4) == 1.0 and t.quantile(0.9) == 3.0
    assert t.max_s == 3.0


def test_trace_ingestion_json_and_csv(tmp_path):
    samples = list(np.random.default_rng(0).lognormal(-1.0, 0.5, 200))
    pj = tmp_path / "trace.json"
    pj.write_text(json.dumps({"latency_s": samples}))
    pc = tmp_path / "trace.csv"
    pc.write_text("client,latency_s\n"
                  + "\n".join(f"{i % 5},{s}" for i, s in enumerate(samples)))
    tj = LatencyTable.from_trace(str(pj), n_bins=8)
    tc = LatencyTable.from_trace(str(pc), n_bins=8)
    assert tj == tc                       # same samples, same histogram
    assert min(samples) <= tj.mean() <= max(samples)
    # pre-quantized table JSON passes through exactly
    pq = tmp_path / "table.json"
    pq.write_text(tj.to_json())
    assert LatencyTable.from_trace(str(pq)) == tj
    scn = scenario_from_trace(str(pj), name="measured")
    assert scn.name == "measured" and isinstance(scn.availability, AlwaysOn)
    # headerless CSV: first column
    ph = tmp_path / "bare.csv"
    ph.write_text("\n".join(str(s) for s in samples))
    assert LatencyTable.from_trace(str(ph), n_bins=8) == tc
    with pytest.raises(ValueError):
        LatencyTable.from_trace(str(tmp_path / "trace.txt"))
    # a header without latency_s must not silently guess a column
    pb = tmp_path / "bad.csv"
    pb.write_text("client,latency\n1,0.5\n2,0.7\n")
    with pytest.raises(ValueError, match="latency_s"):
        LatencyTable.from_trace(str(pb))


# --- alias sampling on the threefry chain ----------------------------------

def _chi2_bound(df: int, z: float = 5.0) -> float:
    """Normal-approx upper band: chi2_df < df + z * sqrt(2 df)."""
    return df + z * np.sqrt(2.0 * df)


def test_alias_sampling_chi_square_matches_table():
    """On-device alias draws over fold_in keys reproduce the bin
    probabilities (the satellite acceptance test)."""
    t = LatencyTable.from_lognormal(0.3, 0.8, 10)
    prob, alias = (jnp.asarray(a) for a in t.alias_arrays())
    N = 1 << 15
    base = jax.random.PRNGKey(7)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        base, jnp.arange(N))
    j = np.asarray(alias_sample(key_uniforms(keys), prob, alias))
    counts = np.bincount(j, minlength=len(t.probs))
    expected = np.asarray(t.probs) * N
    assert (expected > 5).all()           # chi-square validity
    chi2 = float(((counts - expected) ** 2 / expected).sum())
    assert chi2 < _chi2_bound(len(t.probs) - 1), (chi2, counts)


def test_update_ticks_deterministic_and_message_addressed():
    """Draws are pure functions of (client, round): recomputing gives
    identical ticks; changing the round changes them."""
    scn = Scenario("s", LatencyTable.from_uniform(1.0, 50.0, 8))
    plan = scenario_plan(scn, C=16, seed=3, dt=1.0)
    i0 = jnp.zeros(16, jnp.int32)
    a = np.asarray(plan.host_update_ticks(i0))
    b = np.asarray(plan.host_update_ticks(i0))
    np.testing.assert_array_equal(a, b)
    c = np.asarray(plan.host_update_ticks(i0 + 1))
    assert (a != c).any()
    assert (a >= 1).all() and (a <= plan.max_lat_ticks).all()
    bc = plan.host_broadcast_ticks(2)
    np.testing.assert_array_equal(bc, plan.host_broadcast_ticks(2))
    assert (bc != plan.host_broadcast_ticks(3)).any()


# --- per-client latency tables ----------------------------------------------

def test_per_client_table_gather_chi_square():
    """Each client's empirical draw distribution matches ITS assigned
    table (the [T, K]-stack + table_id gather), pinned per client by a
    chi-square test over the message-addressed update draws."""
    tA = LatencyTable.from_uniform(1.0, 5.0, 4)
    tB = LatencyTable((10.0, 20.0, 40.0), (0.5, 0.3, 0.2))
    scn = Scenario("pc", (tA, tB),
                   assignment=TableAssignment("explicit",
                                              table_id=(0, 1, 1, 0)))
    plan = scenario_plan(scn, C=4, seed=5)
    N = 1024
    draws = np.stack([plan.update_latencies_s(i) for i in range(N)])
    for c, t in zip(range(4), (tA, tB, tB, tA)):
        vals = np.asarray(t.values, np.float32)
        j = np.argmin(np.abs(draws[:, c][:, None]
                             - vals[None, :].astype(np.float64)), axis=1)
        counts = np.bincount(j, minlength=len(vals))
        expected = np.asarray(t.probs) * N
        assert (expected > 5).all()
        chi2 = float(((counts - expected) ** 2 / expected).sum())
        assert chi2 < _chi2_bound(len(vals) - 1), (c, chi2, counts)


def test_per_client_tables_event_bins_match_cohort_ticks():
    """The event simulator's continuous-seconds draw and the cohort
    engines' tick draw pick the SAME bin for every message, including
    under per-client tables — ticks are exactly the legacy
    max(1, ceil(s / dt)) quantization of the seconds."""
    scn = Scenario("pc2", (LatencyTable.from_uniform(1.0, 50.0, 8),
                           LatencyTable.from_lognormal(4.0, 0.6, 6)))
    dt = 3.0
    pt = scenario_plan(scn, C=6, seed=11, dt=dt)
    ps = scenario_plan(scn, C=6, seed=11)
    for i in range(4):
        iv = jnp.full(6, i, jnp.int32)
        ticks = np.asarray(pt.host_update_ticks(iv))
        secs = ps.update_latencies_s(i)
        np.testing.assert_array_equal(
            ticks, np.maximum(1, np.ceil(secs / dt)).astype(np.int64))
    bc_t = np.asarray(pt.host_broadcast_ticks(2))
    bc_s = ps.broadcast_latencies_s(2)
    np.testing.assert_array_equal(
        bc_t, np.maximum(1, np.ceil(bc_s / dt)).astype(np.int64))


def test_update_latencies_s_batched_matches_scalar_and_caches():
    scn = Scenario("b", LatencyTable.from_uniform(0.5, 3.0, 8))
    plan = scenario_plan(scn, C=8, seed=1)
    vec = plan.update_latencies_s(3)
    for c in range(8):
        assert plan.update_latency_s(c, 3) == vec[c]
    assert plan.update_latencies_s(3) is vec          # cached per round
    assert (plan.update_latencies_s(4) != vec).any()


def test_table_assignment_kinds_and_validation():
    tabs = (LatencyTable.constant(1.0), LatencyTable.constant(2.0),
            LatencyTable.constant(3.0))
    cyc = TableAssignment("cycle").resolve(7, 3, seed=0)
    np.testing.assert_array_equal(cyc, np.arange(7) % 3)
    drawn = TableAssignment("draw").resolve(256, 3, seed=0)
    assert set(drawn) == {0, 1, 2}
    # drawn assignment is deterministic in the seed
    np.testing.assert_array_equal(
        drawn, TableAssignment("draw").resolve(256, 3, seed=0))
    w = TableAssignment("draw", weights=(1.0, 0.0, 0.0)).resolve(
        64, 3, seed=1)
    assert (w == 0).all()
    with pytest.raises(ValueError, match="table_id length"):
        scenario_plan(Scenario(
            "bad", tabs,
            assignment=TableAssignment("explicit", table_id=(0, 1))),
            C=4, seed=0)
    with pytest.raises(ValueError, match="lie in"):
        TableAssignment("explicit", table_id=(0, 3, 1, 0)).resolve(
            4, 3, seed=0)
    with pytest.raises(ValueError, match="one weight per table"):
        TableAssignment("draw", weights=(0.5, 0.5)).resolve(4, 3, seed=0)
    with pytest.raises(ValueError, match="cycle|explicit|draw"):
        TableAssignment("nope")
    with pytest.raises(ValueError, match="table_id"):
        TableAssignment("explicit")


def test_table_assignment_draw_is_threefry_addressed_and_pinned():
    """kind='draw' ids come from the TABLE_SALT threefry chain — the
    jitted derivation equals resolve() (the multi-host prerequisite:
    every host re-derives the ids in-jit from the seed), and the exact
    ids are pinned so the chain never drifts silently."""
    from repro.scenarios.registry import draw_table_ids
    ids = TableAssignment("draw", weights=(0.6, 0.4)).resolve(8, 2,
                                                              seed=3)
    jit_ids = jax.jit(draw_table_ids,
                      static_argnames=("C", "T", "weights"))(
        8, 2, (0.6, 0.4), jnp.int32(3))
    np.testing.assert_array_equal(ids, np.asarray(jit_ids))
    np.testing.assert_array_equal(ids, [0, 0, 0, 0, 1, 1, 0, 0])
    uni = TableAssignment("draw").resolve(12, 3, seed=0)
    np.testing.assert_array_equal(uni, [2, 0, 0, 1, 1, 2, 0, 0, 2, 1,
                                        1, 1])


def test_error_paths_tables_and_legacy_specs(tmp_path):
    from repro.scenarios import legacy_latency_scenario
    with pytest.raises(ValueError, match="0 < lo <= hi"):
        LatencyTable.from_uniform(0.5, 0.1)
    with pytest.raises(ValueError, match="lo <= hi"):
        legacy_latency_scenario((0.5, 0.1))
    with pytest.raises(ValueError, match="positive and finite"):
        LatencyTable.from_samples([0.5, -1.0])
    with pytest.raises(ValueError, match="empty latency trace"):
        LatencyTable.from_samples([])
    pe = tmp_path / "empty.csv"
    pe.write_text("\n")
    with pytest.raises(ValueError, match="empty latency trace"):
        LatencyTable.from_trace(str(pe))
    ph = tmp_path / "only_header.csv"
    ph.write_text("client,latency_s\n")
    with pytest.raises(ValueError, match="empty latency trace"):
        LatencyTable.from_trace(str(ph))
    pz = tmp_path / "zero.json"
    pz.write_text(json.dumps({"values": [1.0, 2.0], "probs": [0.0, 0.0]}))
    with pytest.raises(ValueError, match="sum to > 0"):
        LatencyTable.from_trace(str(pz))
    with pytest.raises(ValueError, match="ring_cap"):
        Scenario("r", LatencyTable.constant(1.0), ring_cap=1)
    with pytest.raises(TypeError, match="LatencyTable"):
        Scenario("t", 3.0)


def test_per_client_trace_ingestion(tmp_path):
    rng = np.random.default_rng(0)
    fast = list(0.05 + 0.05 * rng.random(100))
    slow = list(1.0 + rng.random(100))
    pj = tmp_path / "per_client.json"
    pj.write_text(json.dumps({"clients": {"7": slow, "3": fast}}))
    pc = tmp_path / "per_client.csv"
    pc.write_text("client,latency_s\n"
                  + "\n".join(f"3,{s}" for s in fast)
                  + "\n" + "\n".join(f"7,{s}" for s in slow))
    sj = scenario_from_trace(str(pj), per_client=True, n_bins=4)
    sc = scenario_from_trace(str(pc), per_client=True, n_bins=4)
    assert sj.tables == sc.tables           # ids sort numerically
    assert len(sj.tables) == 2
    assert sj.tables[0].mean() < 0.2 < sj.tables[1].mean()
    assert sj.assignment.kind == "cycle"
    # engine clients alternate tables 0/1 cyclically
    plan = scenario_plan(sj, C=4, seed=0, dt=0.05)
    np.testing.assert_array_equal(plan.table_id, [0, 1, 0, 1])
    assert plan.max_lat_ticks > 1
    pb = tmp_path / "no_client.csv"
    pb.write_text("latency_s\n0.5\n")
    with pytest.raises(ValueError, match="client"):
        scenario_from_trace(str(pb), per_client=True)
    pm = tmp_path / "flat.json"
    pm.write_text(json.dumps([0.1, 0.2]))
    with pytest.raises(ValueError, match="clients"):
        scenario_from_trace(str(pm), per_client=True)


# --- availability models ----------------------------------------------------

def test_diurnal_tick_mask_and_windows_agree_on_duty():
    av = Diurnal(period_s=64.0, on_frac=0.5)
    mask = av.tick_plan(C=8, dt=1.0, seed=0)
    on = np.mean([np.asarray(mask(jnp.int32(t))).mean()
                  for t in range(128)])
    assert abs(on - 0.5) < 0.1
    w = av.windows(C=8, seed=0)
    for c in range(8):
        assert abs(w.on_time(c, 0.0, 640.0) / 640.0 - 0.5) < 1e-6
        # advance() inverts on_time()
        t1 = w.advance(c, 3.0, 10.0)
        assert abs(w.on_time(c, 3.0, t1) - 10.0) < 1e-9


def test_churn_mask_duty_and_validation():
    av = Churn(p_available=0.7, epoch_s=2.0)
    mask = av.tick_plan(C=64, dt=1.0, seed=0)
    on = np.mean([np.asarray(mask(jnp.int32(t))).mean()
                  for t in range(0, 64, 2)])
    assert abs(on - 0.7) < 0.15
    with pytest.raises(ValueError):
        Churn(p_available=0.0)
    with pytest.raises(ValueError):
        Diurnal(on_frac=1.5)


def test_regional_churn_duty_correlation_and_validation():
    """Within-region availability is positively correlated (the shared
    per-(epoch, region) outage factor), cross-region draws stay
    independent, and the marginal duty is the advertised p_available."""
    av = RegionalChurn(n_regions=2, p_available=0.7, p_region_up=0.8,
                       epoch_s=4.0)
    assert av.duty == 0.7
    C, E = 16, 384
    mask = av.tick_plan(C=C, dt=1.0, seed=3)
    reg = av.regions(C)
    # one sample per epoch: draws are independent across epochs
    M = np.stack([np.asarray(mask(jnp.int32(4 * e))) for e in range(E)])
    duty = M.mean()
    assert abs(duty - 0.7) < 0.05
    X = M.astype(np.float64)
    corr = np.corrcoef(X.T)
    same = reg[:, None] == reg[None, :]
    off_diag = ~np.eye(C, dtype=bool)
    within = corr[same & off_diag]
    cross = corr[~same]
    # analytic within-region corr: (p^2/p_reg - p^2) / (p (1-p)) ~ 0.58
    assert within.mean() > 0.3, within.mean()
    assert abs(cross.mean()) < 0.1, cross.mean()
    # explicit region ids + validation
    av2 = RegionalChurn(n_regions=2, region_of=(0, 0, 1, 1))
    np.testing.assert_array_equal(av2.regions(4), [0, 0, 1, 1])
    with pytest.raises(ValueError, match="region_of"):
        av2.regions(3)                       # length mismatch
    with pytest.raises(ValueError, match="lie in"):
        RegionalChurn(n_regions=2, region_of=(0, 5))
    with pytest.raises(ValueError, match="p_region_up"):
        RegionalChurn(p_available=0.9, p_region_up=0.5)


def test_renewal_churn_exact_schedule_duty_and_validation():
    """Path-wise contract: the cohort tick mask and the event sim's
    renewal windows consume the SAME per-(client, epoch) holding times
    from the fold_in chain, so when dt divides the epoch length exactly
    the mask equals the windows state at EVERY tick — an exact-schedule
    assertion, not just the duty chi-square (kept as backstop)."""
    av = RenewalChurn(on_rate=1.0 / 4.0, off_rate=1.0 / 12.0)
    duty = av.duty
    assert abs(duty - 0.75) < 1e-12
    # mean_cycle = 4 + 12 = 16 s, epoch_cycles = 4 -> E_s = 64 s; dt = 1
    # divides it exactly, so tick t and second t share (epoch, offset)
    assert av.epoch_cycles * av.mean_cycle_s == 64.0
    C, E = 32, 64
    mask = av.tick_plan(C=C, dt=1.0, seed=0)
    w = av.windows(C=C, seed=0)
    # exact schedule across three epochs incl. both epoch boundaries
    for t in range(0, 3 * 64 + 1, 3):
        m = np.asarray(mask(jnp.int32(t)))
        ws = np.array([w.on_at(c, float(t)) for c in range(C)])
        np.testing.assert_array_equal(m, ws, err_msg=f"t={t}")
    epoch_t = max(1, round(av.epoch_cycles * av.mean_cycle_s / 1.0))
    # one sample per epoch and client: independent Bernoulli(duty)
    on = sum(int(np.asarray(mask(jnp.int32(e * epoch_t + 3))).sum())
             for e in range(E))
    n = C * E
    exp_on, exp_off = n * duty, n * (1.0 - duty)
    chi2 = ((on - exp_on) ** 2 / exp_on
            + ((n - on) - exp_off) ** 2 / exp_off)
    assert chi2 < _chi2_bound(1), (chi2, on / n)
    # event-side: continuous on-time fraction integrates to the duty
    frac = np.mean([w.on_time(c, 0.0, 4000.0) / 4000.0 for c in range(8)])
    assert abs(frac - duty) < 0.05
    # advance() inverts on_time() across switch AND epoch boundaries
    for (c, t0, work) in [(0, 3.0, 25.0), (1, 0.0, 70.0), (2, 60.0, 5.0)]:
        t1 = w.advance(c, t0, work)
        assert abs(w.on_time(c, t0, t1) - work) < 1e-9, (c, t0, work)
    with pytest.raises(ValueError, match="on_rate"):
        RenewalChurn(on_rate=0.0)
    with pytest.raises(ValueError, match="epoch_cycles"):
        RenewalChurn(epoch_cycles=10.0, n_draws=8)


def test_masked_client_accrues_no_credit_and_sends_no_update():
    """The availability invariant, pinned at the engine level: while a
    client's window is off it takes no step, accrues no credit, and
    sends nothing — the cohort advances without it."""
    task = _task()
    C = 3
    # phases put client 0 OFF at t=0 (its window opens half a period in)
    av = Diurnal(period_s=1024.0, on_frac=0.5)
    scn = Scenario("inv", LatencyTable.constant(1.0), av)
    sim = CohortSimulator(task, n_clients=C, sizes_per_client=[64] * 4,
                          round_stepsizes=[0.1] * 4, d=2, seed=0,
                          block=8, scenario=scn)
    eng = sim.engine
    off0 = ~np.asarray(eng._plan.host_avail(1))
    assert off0.any() and (~off0).any(), "want a mixed on/off fleet"
    for _ in range(8):
        eng.step()
    st = eng.state
    assert (st.h[off0] == 0).all() and (st.credit[off0] == 0).all()
    assert (st.i[off0] == 0).all()
    assert (st.h[~off0] > 0).all() or (st.i[~off0] > 0).all()
    assert eng.total_messages == int(np.sum(st.i[~off0]))


def test_speed_models_normalized_and_long_tailed():
    for kind in ("uniform", "bimodal", "zipf", "lognormal"):
        s = SpeedModel(kind=kind).draw(256, seed=1)
        assert s.shape == (256,) and s.max() == 1.0 and s.min() > 0.0
    z = SpeedModel(kind="zipf", alpha=0.8).draw(256, seed=1)
    assert z.min() < 0.02                 # long tail reaches slow devices
    with pytest.raises(ValueError):
        SpeedModel(kind="nope").draw(4, seed=0)


# --- registry ---------------------------------------------------------------

def test_registry_presets_resolve():
    assert {"uniform", "mobile_diurnal", "iot_straggler",
            "geo_regional", "sensor_renewal"} <= set(scenario_names())
    scn = get_scenario("mobile_diurnal")
    assert get_scenario(scn) is scn       # passthrough
    with pytest.raises(KeyError):
        get_scenario("nope")
    with pytest.raises(TypeError):
        get_scenario(3.0)


@pytest.mark.parametrize("name", ["uniform", "mobile_diurnal",
                                  "iot_straggler"])
def test_presets_run_on_both_cohort_engines_bit_identical(name):
    """Every preset completes on host-cohort and device engines with
    bit-identical trajectories (the tentpole acceptance criterion)."""
    task = _task(sample_seed=5)
    kw = dict(n_clients=6, sizes_per_client=[4, 6], d=2, seed=2,
              round_stepsizes=[0.1, 0.08], block=4, scenario=name)
    res_co = CohortSimulator(task, **kw).run(max_rounds=2)
    res_dv = DeviceCohortSimulator(task, **kw).run(max_rounds=2)
    assert res_co["final"]["round"] == res_dv["final"]["round"] == 2
    assert res_co["final"]["messages"] == res_dv["final"]["messages"]
    np.testing.assert_array_equal(np.asarray(res_co["model"]["w"]),
                                  np.asarray(res_dv["model"]["w"]))
    assert float(res_co["model"]["b"]) == float(res_dv["model"]["b"])


# --- unified spec across engines -------------------------------------------

def test_three_way_parity_under_stochastic_scenario():
    """d=1 under a stochastic scenario (empirical latency table +
    diurnal availability): host-cohort and device are bit-identical,
    and both match the event simulator's trajectory to float tolerance
    (same argument as the deterministic-latency parity: at d=1 arrival
    timing only reorders float sums)."""
    task = _task(n=500, d=16, seed=7, sample_seed=13)
    scn = Scenario("stoch", LatencyTable.from_lognormal(2.0, 0.7, 8),
                   Diurnal(period_s=64.0, on_frac=0.6))
    kw = dict(n_clients=4, sizes_per_client=[[10, 20, 30, 40]] * 4,
              round_stepsizes=[0.1, 0.08, 0.06, 0.05], d=1, seed=0,
              speeds=[1.0, 0.8, 1.2, 0.9], scenario=scn)
    res_ev = AsyncFLSimulator(task, **kw).run(max_rounds=4)
    res_co = CohortSimulator(task, block=8, **kw).run(max_rounds=4)
    res_dv = DeviceCohortSimulator(task, block=8, **kw).run(max_rounds=4)
    assert (res_ev["final"]["round"] == res_co["final"]["round"]
            == res_dv["final"]["round"] == 4)
    assert (res_ev["final"]["messages"] == res_co["final"]["messages"]
            == res_dv["final"]["messages"])
    np.testing.assert_array_equal(np.asarray(res_co["model"]["w"]),
                                  np.asarray(res_dv["model"]["w"]))
    assert float(res_co["model"]["b"]) == float(res_dv["model"]["b"])
    np.testing.assert_allclose(np.asarray(res_ev["model"]["w"]),
                               np.asarray(res_dv["model"]["w"]),
                               atol=1e-4)


def test_stochastic_scenario_parity_with_dp_and_gate():
    """DP noise + round clip + d=2 + churn + multi-tick stochastic
    latency: host-cohort vs device stays bit-identical (extends the
    deterministic-latency DP parity test to stochastic scenarios)."""
    task = _task(dp_clip=0.1, dp_sigma=2.0)
    scn = Scenario("dpchurn", LatencyTable.from_uniform(4.0, 40.0, 6),
                   Churn(p_available=0.8, epoch_s=8.0))
    kw = dict(n_clients=5, sizes_per_client=[4, 6, 8],
              round_stepsizes=[0.1, 0.08, 0.06], d=2, seed=3,
              speeds=[1.0, 0.6, 1.4, 0.8, 1.1], block=4,
              dp_round_clip=0.5, scenario=scn)
    res_co = CohortSimulator(task, **kw).run(max_rounds=3)
    res_dv = DeviceCohortSimulator(task, **kw).run(max_rounds=3)
    np.testing.assert_array_equal(np.asarray(res_co["model"]["w"]),
                                  np.asarray(res_dv["model"]["w"]))
    assert float(res_co["model"]["b"]) == float(res_dv["model"]["b"])
    assert res_co["final"]["messages"] == res_dv["final"]["messages"]
    assert res_co["final"]["broadcasts"] == res_dv["final"]["broadcasts"]


def test_three_way_parity_per_client_tables_with_dp():
    """Heterogeneity v2 acceptance: per-client latency tables + diurnal
    availability at d=1 — event vs cohort trajectory-equal (at d=1
    arrival timing only reorders float sums), host-cohort vs device
    bitwise, and STILL bitwise once DP noise + round clip are on (DP
    noise chains differ between the event and cohort engines by design,
    so the event leg of the DP comparison is message-count only)."""
    scn = Scenario(
        "pc3", (LatencyTable.from_lognormal(2.0, 0.7, 8),
                LatencyTable.from_uniform(1.0, 20.0, 6)),
        Diurnal(period_s=64.0, on_frac=0.6),
        assignment=TableAssignment("explicit", table_id=(0, 1, 1, 0)))
    kw = dict(n_clients=4, sizes_per_client=[[10, 20, 30]] * 4,
              round_stepsizes=[0.1, 0.08, 0.06], d=1, seed=0,
              speeds=[1.0, 0.8, 1.2, 0.9], scenario=scn)
    task = _task(n=500, d=16, seed=7, sample_seed=13)
    res_ev = AsyncFLSimulator(task, **kw).run(max_rounds=3)
    res_co = CohortSimulator(task, block=8, **kw).run(max_rounds=3)
    res_dv = DeviceCohortSimulator(task, block=8, **kw).run(max_rounds=3)
    assert (res_ev["final"]["messages"] == res_co["final"]["messages"]
            == res_dv["final"]["messages"])
    np.testing.assert_array_equal(np.asarray(res_co["model"]["w"]),
                                  np.asarray(res_dv["model"]["w"]))
    assert float(res_co["model"]["b"]) == float(res_dv["model"]["b"])
    np.testing.assert_allclose(np.asarray(res_ev["model"]["w"]),
                               np.asarray(res_dv["model"]["w"]),
                               atol=1e-4)
    # DP leg: same scenario, noise + round clip on — host-vs-device
    # stays bit-identical, messages match the event engine's schedule
    task_dp = _task(n=500, d=16, seed=7, sample_seed=13, dp_clip=0.1,
                    dp_sigma=1.0)
    dp_co = CohortSimulator(task_dp, block=8, dp_round_clip=0.5,
                            **kw).run(max_rounds=3)
    dp_dv = DeviceCohortSimulator(task_dp, block=8, dp_round_clip=0.5,
                                  **kw).run(max_rounds=3)
    np.testing.assert_array_equal(np.asarray(dp_co["model"]["w"]),
                                  np.asarray(dp_dv["model"]["w"]))
    assert float(dp_co["model"]["b"]) == float(dp_dv["model"]["b"])
    assert dp_co["final"]["messages"] == dp_dv["final"]["messages"] \
        == res_ev["final"]["messages"]


def test_regional_churn_parity_with_dp_and_gate():
    """RegionalChurn (correlated outages) + DP + round clip + d=2 +
    multi-tick latency: host-cohort vs device stays bit-identical."""
    task = _task(dp_clip=0.1, dp_sigma=2.0)
    scn = Scenario("regdp", LatencyTable.from_uniform(4.0, 40.0, 6),
                   RegionalChurn(n_regions=2, p_available=0.8,
                                 p_region_up=0.9, epoch_s=8.0))
    kw = dict(n_clients=5, sizes_per_client=[4, 6, 8],
              round_stepsizes=[0.1, 0.08, 0.06], d=2, seed=3,
              speeds=[1.0, 0.6, 1.4, 0.8, 1.1], block=4,
              dp_round_clip=0.5, scenario=scn)
    res_co = CohortSimulator(task, **kw).run(max_rounds=3)
    res_dv = DeviceCohortSimulator(task, **kw).run(max_rounds=3)
    np.testing.assert_array_equal(np.asarray(res_co["model"]["w"]),
                                  np.asarray(res_dv["model"]["w"]))
    assert float(res_co["model"]["b"]) == float(res_dv["model"]["b"])
    assert res_co["final"]["messages"] == res_dv["final"]["messages"]
    assert res_co["final"]["broadcasts"] == res_dv["final"]["broadcasts"]


def test_renewal_churn_runs_on_all_three_engines():
    """RenewalChurn is the churn model the event simulator ACCEPTS
    (continuous renewal windows in its lazy-advance schedule) — it
    completes the run; the cohort engines run their per-tick
    approximation bit-identically to each other."""
    task = _task(sample_seed=3)
    scn = Scenario("ren", LatencyTable.constant(0.05),
                   RenewalChurn(on_rate=1.0 / 8.0, off_rate=1.0 / 24.0))
    kw = dict(n_clients=4, sizes_per_client=[8, 12],
              round_stepsizes=[0.1, 0.08], d=1, seed=1)
    res_ev = AsyncFLSimulator(task, scenario=scn, **kw).run(max_rounds=2)
    res_co = CohortSimulator(task, block=8, scenario=scn,
                             **kw).run(max_rounds=2)
    res_dv = DeviceCohortSimulator(task, block=8, scenario=scn,
                                   **kw).run(max_rounds=2)
    assert (res_ev["final"]["round"] == res_co["final"]["round"]
            == res_dv["final"]["round"] == 2)
    # off-windows stretch virtual time on every engine
    assert res_ev["final"]["time"] > 0.0
    np.testing.assert_array_equal(np.asarray(res_co["model"]["w"]),
                                  np.asarray(res_dv["model"]["w"]))
    assert float(res_co["model"]["b"]) == float(res_dv["model"]["b"])
    # d=1 hard gate: same message count on every engine regardless of
    # which churn sample path each engine realizes
    assert res_co["final"]["messages"] == res_dv["final"]["messages"]


def test_overflow_bucket_bounded_ring_and_parity():
    """Heavy-tail ring cost acceptance: with a latency tail spanning
    far more ticks than Scenario.ring_cap, the device engine's update
    ring (and unrolled scatter) stays bounded at next_pow2(ring_cap)
    while far arrivals route through the overflow bucket — and the
    trajectory stays bit-identical to the host engine, which splits its
    arrival buckets at the same plan boundary."""
    task = _task(dp_clip=0.1, dp_sigma=2.0)
    scn = Scenario("tail", LatencyTable.from_uniform(1.0, 200.0, 16),
                   ring_cap=8)
    kw = dict(n_clients=6, sizes_per_client=[4, 6], d=2, seed=2,
              round_stepsizes=[0.1, 0.08], block=4, dp_round_clip=0.5,
              scenario=scn)
    co = CohortSimulator(task, **kw)
    dv = DeviceCohortSimulator(task, **kw)
    eng = dv.engine
    assert eng.L == 8                        # capped, not next_pow2(51)
    assert eng._plan.max_lat_ticks > eng.L   # tail really exceeds it
    assert eng.F > 0                         # overflow path is active
    res_co = co.run(max_rounds=3)
    res_dv = dv.run(max_rounds=3)
    np.testing.assert_array_equal(np.asarray(res_co["model"]["w"]),
                                  np.asarray(res_dv["model"]["w"]))
    assert float(res_co["model"]["b"]) == float(res_dv["model"]["b"])
    assert res_co["final"]["messages"] == res_dv["final"]["messages"]
    assert res_co["final"]["broadcasts"] == res_dv["final"]["broadcasts"]


def test_event_sim_scenario_speeds_and_diurnal_slowdown():
    """Scenario speeds flow into the event sim when the caller gives
    none, and diurnal off-windows stretch virtual completion time
    without changing the d=1 trajectory or message count."""
    task = _task(sample_seed=3)
    on = Scenario("on", LatencyTable.constant(0.05))
    dn = Scenario("dn", LatencyTable.constant(0.05),
                  Diurnal(period_s=32.0, on_frac=0.5),
                  SpeedModel(kind="bimodal", slow=0.5, slow_frac=0.5))
    kw = dict(n_clients=4, sizes_per_client=[8, 12],
              round_stepsizes=[0.1, 0.08], d=1, seed=1)
    r_on = AsyncFLSimulator(task, scenario=on, **kw).run(max_rounds=2)
    sim = AsyncFLSimulator(task, scenario=dn, **kw)
    assert len(set(sim.speeds)) > 1       # bimodal draw applied
    r_dn = sim.run(max_rounds=2)
    assert r_on["final"]["round"] == r_dn["final"]["round"] == 2
    assert r_on["final"]["messages"] == r_dn["final"]["messages"]
    assert r_dn["final"]["time"] > r_on["final"]["time"]
    np.testing.assert_allclose(np.asarray(r_on["model"]["w"]),
                               np.asarray(r_dn["model"]["w"]), atol=1e-5)


def test_event_sim_rejects_churn_scenario():
    task = _task()
    kw = dict(n_clients=2, sizes_per_client=[2], round_stepsizes=[0.1],
              d=1, seed=0)
    with pytest.raises(ValueError, match="continuous"):
        AsyncFLSimulator(task, scenario="iot_straggler", **kw)
    # regional churn is tick-hash addressed too — rejected the same way
    with pytest.raises(ValueError, match="continuous"):
        AsyncFLSimulator(task, scenario="geo_regional", **kw)


def test_scenario_and_legacy_latency_are_exclusive():
    task = _task()
    kw = dict(n_clients=2, sizes_per_client=[2], round_stepsizes=[0.1],
              d=1, seed=0)
    with pytest.raises(ValueError, match="not both"):
        CohortSimulator(task, scenario="uniform",
                        latency_fn=lambda r: 1.0, **kw)
    with pytest.raises(ValueError, match="not both"):
        DeviceCohortSimulator(task, scenario="uniform", latency=1.0, **kw)
    with pytest.raises(ValueError, match="not both"):
        AsyncFLSimulator(task, scenario="uniform",
                         latency_fn=lambda r: 1.0, **kw)


def test_fl_config_scenario_flows_through_make_simulator():
    from repro.cohort import make_simulator
    from repro.configs.base import FLConfig
    task = _task()
    cfg = FLConfig(engine="device", cohort_block=4,
                   scenario="mobile_diurnal")
    sim = make_simulator(cfg, task, n_clients=4, sizes_per_client=[2],
                         round_stepsizes=[0.1], d=1, seed=0)
    assert sim.engine._plan.scenario.name == "mobile_diurnal"
    res = sim.run(max_rounds=1)
    assert res["final"]["round"] == 1


@pytest.mark.parametrize("engine_cls", [CohortSimulator,
                                        DeviceCohortSimulator])
def test_heavy_latency_tail_no_spurious_stall(engine_cls):
    """Regression (satellite): max_ticks scaled only by speed ratio, so
    a latency tail spanning many ticks per message outlived the budget
    and raised a bogus 'cohort engine stalled' RuntimeError."""
    task = _task(n=200, d=8, seed=5, sample_seed=2)
    scn = Scenario("tail", LatencyTable.constant(400.0))
    res = engine_cls(
        task, n_clients=2, sizes_per_client=[4] * 20,
        round_stepsizes=[0.1] * 20, d=1, seed=0, block=4,
        scenario=scn).run(max_rounds=20, eval_every=20)
    assert res["final"]["round"] == 20
