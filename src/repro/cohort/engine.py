"""Tick-driven cohort engine: Algorithms 1–4 over stacked client state.

Virtual time is quantized into ticks of dt = block / max(speed).  Each
tick every unblocked client earns ``speed * dt`` iteration credit and the
whole population advances in ONE vmapped scan (``CohortTask.run_block``)
— the per-client Python objects and heapq of ``repro.core.simulator``
become a handful of [C, D] array ops, which is what makes thousands of
clients per process feasible.

Ordering within a tick mirrors the event simulator:
  1. the batched server applies the arrival bucket for this tick
     (one pre-weighted [D] vector — segment-sum over the finishing
     cohort instead of C sequential tree_maps), updates the H counts,
     and fires broadcasts for every round that just completed;
  2. due broadcasts are ISRRECEIVE'd in ascending k with a masked
     where(): w ← v̂ − eta_i · U for clients whose freshest-seen k
     increases (stale broadcasts drop out per client, exactly
     Algorithm 4's guard);
  3. the cohort advances: n_c = min(remaining, floor(credit)) masked
     iterations per client, wait-gated clients (i == k + d) excluded;
  4. finishing clients clip/noise their round update with the fused
     ``kernels/cohort_dp`` kernel, their eta-weighted updates are
     bucket-summed by (latency-quantized) arrival tick, and they advance
     to the next round.

Fidelity: with d = 1 broadcasts only ever reach blocked clients (U = 0,
so ISRRECEIVE is an exact model replacement) and trajectories match the
event simulator bit-for-bit given a ``sample_seed`` task — the parity
test pins this.  With d > 1, latency quantization reorders same-tick
arrivals; every such schedule is one the asynchronous protocol admits,
so Theorem 1's guarantees still apply, but traces are not message-level
identical to the event engine.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.salts import NOISE_SALT
from repro.cohort.state import (FRAC_BITS, BroadcastRing, CohortState,
                                UpdateBuckets, default_max_ticks,
                                next_pow2, pad_sizes, speed_accrual)
from repro.core.strategies import get_strategy, ring_decay
from repro.kernels.cohort_dp import cohort_clip_noise
from repro.scenarios import get_scenario, scenario_plan
from repro.telemetry import (STALE_BINS, SpanRecorder, build_report,
                             open_trace, staleness_bin, update_msg_bytes)
from repro.telemetry.costs import (OP_BLOCK_TICKS, OP_BUCKET_APPLIES,
                                   OP_CASCADE_TICKS, OP_COMPLETE_TICKS,
                                   OP_DELIVER_ROWS, OP_DELIVER_TICKS,
                                   OP_FAR_GROUPS, OP_FAR_TICKS,
                                   OP_RING_SCATTERS, OP_TICKS, zero_ops)


def _commit(x, dtype=None):
    """Explicit host->device transfer of a host value.

    The steady-segment ticks run under ``jax.transfer_guard("disallow")``
    (parity with ``DeviceCohortEngine.run``), where a dtype-converting
    ``jnp.asarray`` counts as an IMPLICIT transfer and raises; numpy does
    the conversion (IEEE round-to-nearest, bit-identical to XLA's
    convert_element_type) and ``device_put`` commits it explicitly.
    """
    return jax.device_put(np.asarray(x, dtype))


@jax.jit
def _isr_receive(w, U, v, eta, take):
    """Masked Algorithm 4 ISRRECEIVE: w ← v̂ − eta_i · U on take rows."""
    return jnp.where(take[:, None], v[None, :] - eta[:, None] * U, w)


@jax.jit
def _weighted_sum(rows, wgt):
    return jnp.sum(rows * wgt[:, None], axis=0)


@jax.jit
def _apply_contrib(v, contrib):
    return v - contrib


@jax.jit
def _zero_rows(rows, mask):
    return jnp.where(mask[:, None], 0.0, rows)


@jax.jit
def _add_scaled_rows(w, delta, eta, mask):
    """w += eta * delta on masked rows (client-side noise consistency)."""
    return w + jnp.where(mask[:, None], eta[:, None] * delta, 0.0)


def _make_strat_apply(strategy, R: int):
    """Stratified (FedAsync) apply: decay each sender-k row of the
    [R, D] bucket by its staleness against the pre-cascade server_k.
    The device engine consumes the SAME ``ring_decay`` weights (as the
    fused bucket-apply kernel's operand), so the two engines' decayed
    sums are bitwise equal."""
    @jax.jit
    def apply(v, total, server_k):
        dec = ring_decay(strategy, server_k, R)
        return v - jnp.sum(total * dec[:, None], axis=0)
    return apply


def _make_strat_insert(R: int):
    """Stratified bucket insert: merge one finishing group into an
    [R, D] sender-k bucket row-by-row with the device engine's exact
    masked-sum + guarded-add expression (rows with no arrivals keep
    their old value bitwise, not old + 0)."""
    @jax.jit
    def insert(cur, sent, eta, in_g, kmod):
        for r in range(R):
            in_r = in_g & (kmod == r)
            vec = jnp.sum(
                sent * (eta * in_r.astype(jnp.float32))[:, None], axis=0)
            cur = cur.at[r].set(
                jnp.where(jnp.any(in_r), cur[r] + vec, cur[r]))
        return cur
    return insert


class CohortEngine:
    def __init__(self, ctask, *, sizes_per_client,
                 round_stepsizes: Sequence[float], d: int = 1,
                 speeds: Optional[Sequence[float]] = None,
                 latency_fn: Optional[Callable] = None, seed: int = 0,
                 block: int = 64, dp_sigma: float = 0.0,
                 dp_clip: float = 0.0, dp_round_clip: float = 0.0,
                 use_dp_kernel: bool = True,
                 interpret: Optional[bool] = None,
                 scenario=None, trace=None, dp_delta: float = 1e-5,
                 strategy=None):
        if getattr(getattr(ctask, "task", None), "frozen", None) is not None:
            raise ValueError(
                "the host cohort engine does not run a task with a frozen "
                "base (LoRA over a shared model): use engine='device'")
        self.ctask = ctask
        C = ctask.C
        self.C = C
        self.d_gate = int(d)
        self.block = int(block)
        self.rng = np.random.default_rng(seed)
        # network/heterogeneity model: a Scenario (or preset name) drives
        # latency, availability, and — when the caller gives no explicit
        # speeds — the fleet speed draw, all on the shared threefry chain
        # (repro.scenarios).  An explicit latency_fn callable keeps the
        # legacy host-side np-rng path; the two are mutually exclusive.
        if scenario is not None and latency_fn is not None:
            raise ValueError("pass either scenario= or latency_fn=, "
                             "not both")
        scn = (get_scenario(scenario) if scenario is not None
               else None if latency_fn is not None
               else get_scenario("uniform"))
        if speeds is None and scn is not None:
            speeds = scn.speeds(C, seed)
        self.speeds = np.asarray(speeds if speeds is not None
                                 else np.ones(C), np.float64)
        assert len(self.speeds) == C
        self.latency_fn = latency_fn or (lambda r: 0.05 + 0.05 * r.random())
        self.dt = self.block / float(self.speeds.max())
        self._plan = (scenario_plan(scn, C=C, seed=seed, dt=self.dt)
                      if scn is not None else None)
        # integer fixed-point credit accrual (see repro.cohort.state):
        # keeps the tick schedule bit-identical with the device engine
        self.accrual = speed_accrual(self.speeds, self.block)

        self.sizes = pad_sizes(sizes_per_client, C)
        self.etas = np.asarray(round_stepsizes, np.float64)

        v0 = ctask.init_flat()
        self.state = CohortState(
            w=jnp.tile(v0[None, :], (C, 1)),
            U=jnp.zeros((C, ctask.D), jnp.float32),
            v=v0,
            i=np.zeros(C, np.int64), h=np.zeros(C, np.int64),
            k=np.zeros(C, np.int64), credit=np.zeros(C, np.int64))
        self.updates = UpdateBuckets()
        self.bcasts = BroadcastRing()

        # round-completion DP (noise_scale = clip * sigma, as in
        # LogRegTask.add_round_noise; dp_round_clip > 0 additionally clips
        # the whole round update = user-level DP)
        from repro.core.tasks import validate_dp_knobs
        validate_dp_knobs(dp_clip, dp_sigma, "CohortEngine")
        self.dp_sigma = float(dp_sigma)
        self.dp_clip = float(dp_clip)
        self.dp_round_clip = float(dp_round_clip)
        self.use_dp_kernel = bool(use_dp_kernel)
        # interpret=None: infer from the backend — interpret-mode Pallas
        # on CPU (byte-identical to the historical default there), the
        # compiled kernel on a real TPU/GPU
        self.interpret = ((jax.default_backend() == "cpu")
                          if interpret is None else bool(interpret))
        self.noise_base = jax.random.PRNGKey(seed ^ NOISE_SALT)

        # server-side aggregation strategy (repro.core.strategies):
        # the paper default applies [D] arrival buckets on dequeue;
        # FedAsync stratifies buckets by sender-k into [R, D] rings and
        # decays at apply; FedBuff accumulates and flushes every B.
        # R matches the device engine's sender-k ring width.
        self.strategy = get_strategy(strategy)
        self.R = next_pow2(self.d_gate + 2)
        if self.strategy.stratified:
            self._strat_apply = _make_strat_apply(self.strategy, self.R)
            self._strat_insert = _make_strat_insert(self.R)
            self._strat_zero = jnp.zeros((self.R, ctask.D), jnp.float32)
        if self.strategy.buffered:
            self._buf_zero = jnp.zeros((ctask.D,), jnp.float32)
            self._buf_vec = self._buf_zero
            self._buf_cnt = 0

        self.total_messages = 0
        self.total_broadcasts = 0
        self._h_counts: Dict[int, int] = {}     # Algorithm 3's H, per round
        # telemetry: same integer counters the device engine keeps
        # in-loop — the parity contract pins them bitwise equal
        self._upd_bytes = update_msg_bytes(ctask.D)
        self.part = np.zeros(C, dtype=np.int64)
        self.bytes_up = np.zeros(C, dtype=np.int64)
        self.stale_hist = np.zeros(STALE_BINS, dtype=np.int64)
        self.ovf_hwm = 0
        self.far_messages = 0
        # op census (repro.telemetry.costs): numpy mirror of the device
        # engine's in-loop [N_OPS] vector, incremented at the exact same
        # protocol points — the parity contract pins it bitwise equal
        self.ops = zero_ops()
        self.dp_delta = float(dp_delta)
        self._trace = open_trace(trace)
        self.history: List[Dict[str, float]] = []

    # -- host-side gathers --------------------------------------------------
    def _eta_of(self, i: np.ndarray) -> np.ndarray:
        return self.etas[np.minimum(i, len(self.etas) - 1)]

    def _s_of(self, i: np.ndarray) -> np.ndarray:
        cols = np.minimum(i, self.sizes.shape[1] - 1)
        return self.sizes[np.arange(self.C), cols]

    def _latency_ticks(self, n: int) -> np.ndarray:
        """Legacy host-callable path only (explicit latency_fn=): a
        Python loop over self.rng.  Scenario-driven engines draw
        message-addressed ticks from the shared threefry chain instead
        (one vectorized [C] device call, bit-identical to the device
        engine) — see ``_update_ticks`` / ``_bcast_ticks``."""
        lats = np.array([self.latency_fn(self.rng) for _ in range(n)])
        return np.maximum(1, np.ceil(lats / self.dt)).astype(np.int64)

    def _update_ticks(self, idx: np.ndarray, i: np.ndarray) -> np.ndarray:
        """Arrival-tick offsets of the finishing clients ``idx``."""
        if self._plan is not None:
            return self._plan.host_update_ticks(i)[idx]
        return self._latency_ticks(len(idx))

    def _bcast_ticks(self, k: int) -> np.ndarray:
        """Per-client arrival-tick offsets of broadcast ``k``."""
        if self._plan is not None:
            return self._plan.host_broadcast_ticks(k)
        return self._latency_ticks(self.C)

    def _avail(self, t: int) -> Optional[np.ndarray]:
        return self._plan.host_avail(t) if self._plan is not None else None

    # -- one tick -----------------------------------------------------------
    def step(self) -> None:
        st = self.state
        st.tick += 1
        t = st.tick
        self.ops[OP_TICKS] += 1

        # 1) server: apply this tick's arrival bucket, maybe broadcast.
        # far + near in THIS order — the device engine applies
        # overflow + ring_slot the same way (bit parity).
        far, near, pairs = self.updates.pop(t)
        strat = self.strategy
        if far is not None and near is not None:
            total = far + near
        else:
            total = far if far is not None else near
        if total is not None:
            self.ops[OP_BUCKET_APPLIES] += 1
            if strat.stratified:
                # FedAsync: total is [R, D] by sender k; decay rows by
                # staleness against the pre-cascade server_k
                st.v = self._strat_apply(
                    st.v, total, _commit(st.server_k, np.int32))
            elif strat.buffered:
                # FedBuff: bank this tick's arrivals, flush every B
                self._buf_vec = self._buf_vec + total
                self._buf_cnt += len(pairs)
                if self._buf_cnt >= strat.buffer_size:
                    st.v = _apply_contrib(st.v, self._buf_vec)
                    self._buf_vec = self._buf_zero
                    self._buf_cnt = 0
            else:
                st.v = _apply_contrib(st.v, total)
        for r, _c, ks in pairs:
            self._h_counts[r] = self._h_counts.get(r, 0) + 1
            # staleness-at-apply, binned against the PRE-cascade server_k
            # (the device engine reads st.server_k at the same point)
            self.stale_hist[staleness_bin(st.server_k - ks)] += 1
        k_pre_cascade = st.server_k
        while self._h_counts.get(st.server_k, 0) >= self.C:
            del self._h_counts[st.server_k]
            st.server_k += 1
            self.total_broadcasts += 1
            at = t + self._bcast_ticks(st.server_k)
            self.bcasts.push(st.server_k, st.v, at)
        if st.server_k > k_pre_cascade:
            self.ops[OP_CASCADE_TICKS] += 1

        # 2) deliver due broadcasts, ascending k, freshest-wins per client
        # op census: clients whose freshest-seen k advances this tick ==
        # the rows the device engine's delivery gather replaces
        k_before = st.k.copy()
        due = self.bcasts.due(t)
        for b in due:
            take = (b["at"] <= t) & (b["k"] > st.k)
            if take.any():
                eta = _commit(self._eta_of(st.i), np.float32)
                st.w = _isr_receive(st.w, st.U, b["v"], eta,
                                    _commit(take))
                st.k[take] = b["k"]
        if due:
            self.bcasts.retire(t)
        deliver_rows = int(np.sum(st.k > k_before))
        self.ops[OP_DELIVER_ROWS] += deliver_rows
        if deliver_rows:
            self.ops[OP_DELIVER_TICKS] += 1

        # 3) advance the cohort: one vmapped masked block.  Availability
        #    gates compute, credit accrual AND round completion — an off
        #    client accrues nothing and sends nothing this tick.
        active = ~st.blocked(self.d_gate)
        avail = self._avail(t)
        if avail is not None:
            active &= avail
        st.credit[active] += self.accrual[active]
        s_i = self._s_of(st.i)
        n = np.minimum(s_i - st.h, st.credit >> FRAC_BITS)
        n[~active] = 0
        np.maximum(n, 0, out=n)
        nmax = int(n.max())
        if nmax > 0:
            self.ops[OP_BLOCK_TICKS] += 1
            st.credit -= n << FRAC_BITS
            eta = _commit(self._eta_of(st.i), np.float32)
            st.w, st.U = self.ctask.run_block(
                st.w, st.U, _commit(st.i, np.int32),
                _commit(st.h, np.int32), _commit(n, np.int32),
                eta, next_pow2(nmax))
            st.h += n

        # 4) round completions: clip/noise, enqueue, advance round
        done = active & (st.h >= s_i)
        if done.any():
            self._finish_rounds(done)

    def _finish_rounds(self, done: np.ndarray) -> None:
        st = self.state
        idx = np.flatnonzero(done)
        self.ops[OP_COMPLETE_TICKS] += 1
        self.total_messages += len(idx)
        self.part[idx] += 1
        self.bytes_up[idx] += self._upd_bytes
        eta = self._eta_of(st.i)
        done_dev = _commit(done)
        wgt_all = _commit(eta * done, np.float32)

        arrive = np.full(self.C, -1, np.int64)
        arrive[idx] = st.tick + self._update_ticks(idx, st.i)
        groups = np.unique(arrive[idx])

        if self.dp_sigma > 0.0 or self.dp_round_clip > 0.0:
            # commit the tick explicitly: steady segments run under
            # jax.transfer_guard("disallow") and a bare Python int here
            # would be an implicit host->device transfer
            key = jax.random.fold_in(self.noise_base,
                                     _commit(st.tick, np.int32))
            noised, agg = cohort_clip_noise(
                st.U, key, wgt_all, done_dev,
                clip=self.dp_round_clip,
                noise_scale=self.dp_clip * self.dp_sigma,
                use_kernel=self.use_dp_kernel, interpret=self.interpret)
            # client-side consistency (Algorithm 1 line 24): w += eta *
            # (sent − raw) so a later ŵ = v̂ − eta·U replacement stays
            # consistent with the noise the server absorbed.
            st.w = _add_scaled_rows(st.w, noised - st.U,
                                    _commit(eta, np.float32), done_dev)
            sent = noised
        else:
            sent, agg = st.U, None

        # arrival offsets past the plan's ring boundary go to the FAR
        # tier — mirrors the device engine's overflow bucket so the
        # delivery-time float add order matches (see UpdateBuckets)
        ring = (self._plan.ring_ticks if self._plan is not None
                else None)
        strat = self.strategy
        # FedAsync buckets are stratified by sender k (mod R): the k each
        # finishing client will stamp on its message is st.k, pinned here
        # BEFORE the round advance below
        kmod = (st.k & (self.R - 1)) if strat.stratified else None
        far_groups = 0
        for g in groups:
            in_g = arrive == g
            far = ring is not None and int(g) - st.tick >= ring
            members = np.flatnonzero(in_g)
            # op census: a near group is one distinct ring-slot scatter,
            # a far group one overflow-bucket insert — the device engine
            # counts the same masked writes inside do_complete / do_far
            if far:
                far_groups += 1
                self.far_messages += len(members)
            else:
                self.ops[OP_RING_SCATTERS] += 1
            pairs_list = [(int(st.i[c]), int(c), int(st.k[c]))
                          for c in members]
            if strat.stratified:
                cur = self.updates.get(int(g), far=far)
                if cur is None:
                    cur = self._strat_zero
                cur = self._strat_insert(
                    cur, sent, _commit(eta, np.float32),
                    _commit(in_g), _commit(kmod, np.int32))
                self.updates.put(int(g), cur, pairs_list, far=far)
                continue
            if agg is not None and len(groups) == 1:
                vec = agg                       # fused kernel aggregate
            else:
                vec = _weighted_sum(sent, _commit(eta * in_g, np.float32))
            self.updates.add(int(g), vec, pairs_list, far=far)
        if far_groups:
            self.ops[OP_FAR_TICKS] += 1
            self.ops[OP_FAR_GROUPS] += far_groups
        # far-tier occupancy high-water mark == the device engine's peak
        # count of occupied overflow slots (one slot per pending far tick)
        self.ovf_hwm = max(self.ovf_hwm, len(self.updates.far_contrib))

        st.i[done] += 1
        st.h[done] = 0
        st.credit[done] = np.minimum(st.credit[done],
                                     self.block << FRAC_BITS)
        st.U = _zero_rows(sent, done_dev)

    # -- main loop ----------------------------------------------------------
    def run(self, *, max_rounds: int, eval_every: int = 1,
            eval_fn: Optional[Callable] = None,
            max_ticks: Optional[int] = None) -> Dict[str, Any]:
        """Run until the server completes ``max_rounds`` broadcasts.

        Same result schema as ``AsyncFLSimulator.run``.
        """
        if eval_fn is not None:
            evals = lambda vec: eval_fn(self.ctask.unflatten(vec))  # noqa: E731
        else:
            evals = self.ctask.metrics
        st = self.state
        if max_ticks is None:
            tail = (self._plan.max_lat_ticks
                    if self._plan is not None else 1)
            duty = self._plan.duty if self._plan is not None else 1.0
            max_ticks = default_max_ticks(self.sizes, self.speeds,
                                          self.block, max_rounds,
                                          lat_tail_ticks=tail, duty=duty)
        next_eval = eval_every
        # kept on the engine so the timeline CLI (python -m
        # repro.telemetry capture) can export the wall spans after run()
        timer = self.timer = SpanRecorder()
        import time
        run_t0 = time.perf_counter()
        # First segment runs unguarded (jit compiles may stage host
        # constants); once warm, steady-segment ticks run under
        # transfer_guard("disallow") like DeviceCohortEngine.run — any
        # implicit host->device transfer inside a tick is a perf bug.
        # Phase accounting matches the device engine (first_segment /
        # steady / eval), with block_until_ready closing each segment so
        # async tick dispatch can't be charged to the eval that follows.
        guarded = False
        seg_t0 = run_t0
        while st.server_k < max_rounds:
            if st.tick >= max_ticks:
                raise RuntimeError(
                    f"cohort engine stalled: {st.tick} ticks, "
                    f"server_k={st.server_k} < {max_rounds} "
                    f"(in flight: {len(self.updates)} updates, "
                    f"{len(self.bcasts.pending)} broadcasts)")
            if guarded:
                with jax.transfer_guard("disallow"):
                    self.step()
            else:
                self.step()
            if st.server_k >= next_eval:
                jax.block_until_ready(st.v)
                timer.add("first_segment" if not guarded else "steady",
                          time.perf_counter() - seg_t0)
                with timer.phase("eval"):
                    m = evals(st.v)
                    m.update(round=st.server_k, time=st.tick * self.dt,
                             messages=self.total_messages)
                    self.history.append(m)
                    next_eval = st.server_k + eval_every
                    self._emit_segment()
                guarded = True
                seg_t0 = time.perf_counter()
        jax.block_until_ready(st.v)
        timer.add("first_segment" if not guarded else "steady",
                  time.perf_counter() - seg_t0)
        with timer.phase("eval"):
            final = evals(st.v)
        final.update(round=st.server_k, time=st.tick * self.dt,
                     messages=self.total_messages,
                     broadcasts=self.total_broadcasts,
                     overflow_hwm=self.ovf_hwm,
                     far_messages=self.far_messages)
        timer.add("run", time.perf_counter() - run_t0)
        report = self.telemetry_report(wall=timer.as_dict())
        if self._trace:
            self._trace.emit("report", **report.to_dict())
            self._trace.close()
        return {"final": final, "history": self.history,
                "model": self.ctask.unflatten(st.v), "telemetry": report}

    # -- telemetry ----------------------------------------------------------
    def _emit_segment(self) -> None:
        if not self._trace:
            return
        st = self.state
        self._trace.emit(
            "segment", engine="host", round=int(st.server_k),
            tick=int(st.tick), time=int(st.tick) * self.dt,
            messages=self.total_messages,
            broadcasts=self.total_broadcasts,
            bytes_up_total=int(self.bytes_up.sum()),
            staleness_hist=self.stale_hist,
            overflow_hwm=self.ovf_hwm,
            ops=self.ops.copy())

    def telemetry_report(self, wall=None):
        """MetricsReport from the counters accumulated so far."""
        st = self.state
        src_task = getattr(self.ctask, "task", None)
        return build_report(
            engine="host", clients=self.C, flat_dim=self.ctask.D,
            rounds=int(st.server_k), messages=self.total_messages,
            broadcasts=self.total_broadcasts,
            participation=self.part, bytes_up=self.bytes_up,
            staleness_hist=self.stale_hist,
            overflow_hwm=self.ovf_hwm, far_messages=self.far_messages,
            ticks=int(st.tick), ops=self.ops,
            dp_sigma=self.dp_sigma, dp_delta=self.dp_delta,
            n_examples=(int(src_task.X.shape[0])
                        if hasattr(src_task, "X") else None),
            sizes_per_client=self.sizes, wall=wall)
