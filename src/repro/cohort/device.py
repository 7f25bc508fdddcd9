"""Device-resident cohort engine: the whole tick loop as ONE jitted
``lax.while_loop`` over an on-device ``DeviceCohortState``.

``CohortEngine`` (the host-loop engine) batches the heavy [C, D] compute,
but its per-tick control flow lives in Python: every tick costs a handful
of separate device dispatches plus host<->device syncs of the protocol
counters, so at scale wall clock is dominated by dispatch/sync, not by
the hardware.  This engine moves the complete tick — server bucket
apply, H-count merge, broadcast-cascade firing, masked ISRRECEIVE,
credit accrual, block advance, fused clip+noise round completion — into
a single jitted tick function iterated by ``lax.while_loop`` until the
next eval boundary.  The host syncs exactly once per eval segment (one
scalar read of ``server_k``).

The Python-dict ``UpdateBuckets``/``BroadcastRing`` become fixed-capacity
power-of-two ring arrays inside the state pytree (see
``repro.cohort.state.DeviceCohortState`` for the capacity arguments),
and the client axis of every [C, ...] block is sharded over the local
devices via ``repro.sharding.cohort_shardings``, with the state buffer
donated across segments.

The update ring is bounded: its length L (and the unrolled per-slot
bucket scatter) covers latency offsets only up to the plan's
``ring_ticks`` boundary (``Scenario.ring_cap``), and draws quantizing
past it go to an explicit Q-slot OVERFLOW BUCKET — (arrival tick,
pre-weighted [D] vector, [R] round counts) entries merged by exact
arrival tick.  Heavy-tailed tables (``iot_straggler``-class Pareto
tails) therefore no longer scale compile time/memory with
``next_pow2(max latency ticks)``.  The host engine splits its arrival
buckets at the same plan boundary and applies ``v -= far + near`` in
the same order, so the split is invisible to the bit-parity contract;
if the bucket ever exhausts (more distinct far arrival ticks in flight
than Q slots), the segment stops with an error latch and ``run``
raises with the knob to turn.

Fidelity: ticks use the same quantization and the same integer
fixed-point credit (``state.FRAC_BITS``) as the host engine, and sample
draws are (client, round, iteration) addressed, so the two cohort
engines are **bit-identical** — under deterministic latency
(tests/test_cohort_parity.py pins this three ways against the event
simulator) and under stochastic scenarios (tests/test_scenarios.py),
whose latency/availability draws are message-addressed on the shared
threefry chain rather than consumed from a sequential stream.

Network and fleet heterogeneity come from a ``repro.scenarios``
Scenario — an empirical ``LatencyTable`` (alias-method draws on the
shared threefry chain, addressed by message identity), an availability
model (diurnal windows / churn as pure [C]-shaped tick ops), and an
optional speed distribution — never from a host callable, which cannot
cross into the jitted loop.  Latency draws are (client, round) /
(broadcast k, client) addressed, so the host-loop engine draws the
exact same arrival ticks and host-cohort vs device stays
**bit-identical under stochastic scenarios too** (the legacy ``latency``
spec — float seconds or an (lo, hi) range — is adapted onto the same
machinery).  The default ``uniform`` scenario matches the host engines'
legacy default network and quantizes to the same single tick whenever
``dt = block / max(speed) >= 0.1`` — the usual regime.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from repro.analysis.salts import NOISE_SALT
from repro.cohort.state import (FRAC_BITS, DeviceCohortState,
                                default_max_ticks, next_pow2, pad_sizes,
                                speed_accrual)
from repro.core.strategies import get_strategy, ring_decay
from repro.kernels.cohort_dp import cohort_clip_noise
from repro.kernels.tick_fused import (bucket_apply, tick_deliver,
                                      tick_scatter)
from repro.scenarios import (get_scenario, legacy_latency_scenario,
                             scenario_plan)
from repro.sharding import cohort_mesh, cohort_shardings
from repro.telemetry import (STALE_BINS, PhaseTimer, build_report,
                             open_trace, update_msg_bytes)
from repro.telemetry.costs import (N_OPS, OP_BLOCK_TICKS, OP_FAR_GROUPS,
                                   OP_FAR_TICKS, OP_RING_SCATTERS)

# Unroll bound for the overflow bucket's per-completion-tick far-group
# loop: one iteration per distinct far arrival tick.  Most tables have a
# handful of bins past the ring boundary; a union of many fine-binned
# per-client tables is clamped here so the jitted tick never scales
# with the tail — a tick that genuinely produces more distinct far
# groups than this trips the err latch and run() raises with the
# ring_cap advice.
FAR_UNROLL_CAP = 16


def _build_segment(ctask, *, C: int, D: int, block: int, b_stat: int,
                   d_gate: int, L: int, R: int, B: int, Q: int, F: int,
                   plan, dp_clip: float, dp_sigma: float,
                   dp_round_clip: float, use_dp_kernel: bool,
                   interpret: bool, in_kernel_rng: bool,
                   fuse_ticks: bool, seed: int, strategy, mesh=None):
    """Compile the eval-boundary segment runner for one configuration.

    Returns ``segment(state, etas, sizes, accrual, target_k, tick_limit)``
    — a jitted, state-donating function that advances the protocol until
    ``server_k >= target_k`` or the tick budget runs out.  Per-instance
    arrays (etas, sizes, accrual) are arguments rather than closure
    constants so fresh engine instances with the same geometry reuse the
    compiled executable.  ``mesh`` is the client mesh when C is sharded
    over several devices: the fused client kernels then run per shard.
    """
    dp_on = dp_sigma > 0.0 or dp_round_clip > 0.0
    noise_scale = dp_clip * dp_sigma
    ones1 = jnp.ones((1,), jnp.float32)   # unit decay for [1, D] buckets
    # server-side aggregation strategy (repro.core.strategies), resolved
    # at trace time: the paper default applies the due [D] bucket as-is;
    # FedAsync keeps a sender-k-stratified [R, D] twin of each bucket
    # and decays strata at apply; FedBuff banks due buckets and flushes
    # every BUF-th message.  All strategy branches are Python-level, so
    # the default tick's jaxpr — and the goldens it pins — is unchanged.
    stratified = strategy.stratified
    buffered = strategy.buffered
    BUF = strategy.buffer_size if buffered else 0
    noise_base = jax.random.PRNGKey(seed ^ NOISE_SALT)   # == host engine's
    run_block = ctask.block_body(b_stat)
    cidx = jnp.arange(C)
    S = STALE_BINS
    upd_bytes = jnp.int32(update_msg_bytes(D))
    # scenario closures (repro.scenarios.ScenarioPlan): message-addressed
    # latency-tick draws and the availability mask, pure jax ops the host
    # engine evaluates identically — the bit-parity contract
    avail_mask = plan.avail_mask

    def segment(st: DeviceCohortState, etas, sizes, accrual,
                target_k, tick_limit) -> DeviceCohortState:

        def tick_fn(st: DeviceCohortState) -> DeviceCohortState:
            t = st.tick + 1

            # 1) server: pop this tick's arrival bucket (ring slot +
            #    any overflow entry due now), merge H counts,
            #    cascade-fire every round whose H just filled
            slot = t & (L - 1)
            cnt_row = st.upd_cnt[slot]                       # [R]
            ks_row = st.upd_ks[slot]                         # [R]
            if F > 0:
                ovf_hit = st.ovf_at == t                     # [Q]
                # entries merge by arrival tick at insert, so at most
                # one slot is due; the masked sums only run on hit
                # ticks (far arrivals are the latency tail)

                def pop_ovf(_):
                    out = (jnp.sum(st.ovf_vec
                                   * ovf_hit.astype(jnp.float32)[:, None],
                                   axis=0),
                           jnp.sum(st.ovf_cnt
                                   * ovf_hit.astype(jnp.int32)[:, None],
                                   axis=0),
                           jnp.sum(st.ovf_ks
                                   * ovf_hit.astype(jnp.int32)[:, None],
                                   axis=0))
                    if stratified:
                        out += (jnp.sum(
                            st.ovf_kvec
                            * ovf_hit.astype(jnp.float32)[:, None, None],
                            axis=0),)
                    return out

                def no_ovf(_):
                    out = (jnp.zeros((D,), jnp.float32),
                           jnp.zeros((R,), jnp.int32),
                           jnp.zeros((R,), jnp.int32))
                    if stratified:
                        out += (jnp.zeros((R, D), jnp.float32),)
                    return out

                popped = lax.cond(jnp.any(ovf_hit), pop_ovf, no_ovf,
                                  None)
                ovf_vec_t, ovf_cnt_t, ovf_ks_t = popped[:3]
                cnt_total = cnt_row + ovf_cnt_t
                ks_total = ks_row + ovf_ks_t
                # overflow + ring_slot in THIS order — the host engine
                # applies far + near the same way (bit parity)
                arr_due = ovf_vec_t + st.upd_vec[slot]
                kvec_due = (popped[3] + st.upd_kvec[slot]
                            if stratified else None)
                ovf_vec = jnp.where(ovf_hit[:, None], 0.0, st.ovf_vec)
                ovf_at = jnp.where(ovf_hit, 0, st.ovf_at)
                ovf_cnt = jnp.where(ovf_hit[:, None], 0, st.ovf_cnt)
                ovf_ks = jnp.where(ovf_hit[:, None], 0, st.ovf_ks)
                ovf_kvec = (jnp.where(ovf_hit[:, None, None], 0.0,
                                      st.ovf_kvec)
                            if stratified else st.ovf_kvec)
            else:
                cnt_total = cnt_row
                ks_total = ks_row
                arr_due = st.upd_vec[slot]
                kvec_due = st.upd_kvec[slot] if stratified else None
                ovf_vec, ovf_at, ovf_cnt, ovf_ks = (
                    st.ovf_vec, st.ovf_at, st.ovf_cnt, st.ovf_ks)
                ovf_kvec = st.ovf_kvec
            has_arrivals = jnp.sum(cnt_total) > 0
            # bucket apply — fused kernel (kernels/tick_fused): on CPU
            # its reference path traces the engines' historical
            # expressions verbatim (bit parity with _make_strat_apply /
            # v - arr_due); on TPU/GPU it is one Pallas pass over D
            if stratified:
                # FedAsync: decay each sender-k stratum of the due
                # bucket by its staleness — ring_decay is the SHARED
                # expression the host engine jits in _make_strat_apply;
                # here the weights feed the kernel as an operand
                dec = ring_decay(strategy, st.server_k, R)
                v = bucket_apply(st.v, kvec_due, dec, has_arrivals,
                                 mesh=mesh)
                buf_vec, buf_cnt = st.buf_vec, st.buf_cnt
            elif buffered:
                # FedBuff: bank the due bucket, flush (and reset) on
                # every BUF-th banked message — the host engine flushes
                # on the same python-side counter
                buf_vec = jnp.where(has_arrivals,
                                    st.buf_vec + arr_due, st.buf_vec)
                buf_cnt = st.buf_cnt + jnp.sum(cnt_total)
                flush = buf_cnt >= BUF
                v = bucket_apply(st.v, buf_vec[None, :], ones1, flush,
                                 mesh=mesh)
                buf_vec = jnp.where(flush,
                                    jnp.zeros((D,), jnp.float32),
                                    buf_vec)
                buf_cnt = jnp.where(flush, 0, buf_cnt)
            else:
                v = bucket_apply(st.v, arr_due[None, :], ones1,
                                 has_arrivals, mesh=mesh)
                buf_vec, buf_cnt = st.buf_vec, st.buf_cnt
            upd_vec = st.upd_vec.at[slot].set(
                jnp.zeros((D,), jnp.float32))
            upd_cnt = st.upd_cnt.at[slot].set(jnp.zeros((R,), jnp.int32))
            upd_ks = st.upd_ks.at[slot].set(jnp.zeros((R,), jnp.int32))
            upd_kvec = (st.upd_kvec.at[slot].set(
                jnp.zeros((R, D), jnp.float32))
                if stratified else st.upd_kvec)
            h_counts = st.h_counts + cnt_total
            # staleness-at-apply census: slot r of ks_total counts the
            # arrivals whose sender saw broadcast counter r (mod R); the
            # true staleness tau = server_k - k_send is in [0, d-1], so
            # its mod-R residue against the PRE-cascade server_k is
            # exact — the host engine bins the same quantity per pair
            tau = (st.server_k - jnp.arange(R, dtype=jnp.int32)) & (R - 1)
            stale_hist = st.stale_hist.at[
                jnp.minimum(tau, S - 1)].add(ks_total)

            def casc_cond(c):
                sk, hc = c[0], c[1]
                return hc[sk & (R - 1)] >= C

            def casc_body(c):
                sk, hc, bc_v, bc_k, bc_at, nb = c
                hc = hc.at[sk & (R - 1)].set(0)
                sk = sk + 1
                b = sk & (B - 1)
                bc_v = bc_v.at[b].set(v)
                bc_k = bc_k.at[b].set(sk)
                bc_at = bc_at.at[b].set(t + plan.broadcast_ticks(sk))
                return (sk, hc, bc_v, bc_k, bc_at, nb + 1)

            (server_k, h_counts, bc_v, bc_k, bc_at,
             broadcasts) = lax.while_loop(
                casc_cond, casc_body,
                (st.server_k, h_counts, st.bc_v, st.bc_k, st.bc_at,
                 st.broadcasts))

            # 2) masked ISRRECEIVE: freshest due broadcast per client
            #    (ascending-k sequential delivery == keep only max k);
            #    the [C, D] gather+replace only runs on delivery ticks
            elig = (bc_at <= t) & (bc_k[:, None] > st.k[None, :])  # [B, C]
            eta = etas[jnp.minimum(st.i, etas.shape[0] - 1)]       # [C]

            def do_deliver(_):
                cand = jnp.where(elig, bc_k[:, None], 0)
                best = jnp.argmax(cand, axis=0)                    # [C]
                best_k = jnp.max(cand, axis=0)
                take = best_k > st.k
                # fused gather+receive (kernels/tick_fused): the ring
                # gather and the masked ISRRECEIVE in one [C, D] pass;
                # CPU reference = bc_v[best] - eta*U verbatim
                w = tick_deliver(st.w, st.U, bc_v, best, take, eta,
                                 mesh=mesh)
                return w, jnp.where(take, best_k, st.k)

            w, k = lax.cond(jnp.any(elig), do_deliver,
                            lambda _: (st.w, st.k), None)

            # 3) advance the cohort: credit accrual + one masked block.
            #    Availability gates compute, credit AND completion — an
            #    off client accrues nothing and sends nothing this tick.
            active = st.i < k + d_gate
            if avail_mask is not None:
                active = active & avail_mask(t)
            credit = st.credit + jnp.where(active, accrual, 0)
            s_i = sizes[cidx, jnp.minimum(st.i, sizes.shape[1] - 1)]
            n = jnp.where(active,
                          jnp.minimum(s_i - st.h, credit >> FRAC_BITS), 0)
            n = jnp.maximum(n, 0)
            credit = credit - (n << FRAC_BITS)
            # idle ticks (everyone blocked / awaiting credit) skip the
            # block entirely — mirrors the host engine's nmax > 0 guard
            any_block = jnp.any(n > 0)
            w, U = lax.cond(
                any_block,
                lambda ops: run_block(*ops),
                lambda ops: (ops[0], ops[1]),
                (w, st.U, st.i, st.h, n, eta))
            h = st.h + n

            # 4) round completions: clip/noise, bucket scatter, advance —
            #    all [C, D]-sized work gated on any round finishing
            done = active & (h >= s_i)
            done_i32 = done.astype(jnp.int32)
            any_done = jnp.any(done)
            messages = st.messages + jnp.sum(done_i32)
            part = st.part + done_i32
            bytes_up = st.bytes_up + done_i32 * upd_bytes

            # op census (repro.telemetry.costs): branch hits and row
            # counts, int-only so the float math is untouched.  The
            # delivery metrics re-evaluate do_deliver's take-mask
            # OUTSIDE its lax.cond (cheap [B, C] int compares); the
            # host engine counts clients whose k advanced — identical.
            dlv_take = jnp.max(jnp.where(elig, bc_k[:, None], 0),
                               axis=0) > st.k
            deliver_rows = jnp.sum(dlv_take.astype(jnp.int32))
            op_inc = jnp.stack([
                jnp.int32(1),                               # ticks
                any_block.astype(jnp.int32),                # block_ticks
                has_arrivals.astype(jnp.int32),             # bucket_applies
                (server_k > st.server_k).astype(jnp.int32),  # cascade_ticks
                (deliver_rows > 0).astype(jnp.int32),       # deliver_ticks
                deliver_rows,                               # deliver_rows
                jnp.int32(0),                   # ring_scatters (do_complete)
                any_done.astype(jnp.int32),                 # complete_ticks
                jnp.int32(0),                   # far_ticks (do_complete)
                jnp.int32(0),                   # far_groups (do_far)
            ])
            op_census = st.ops + op_inc

            def do_complete(ops):
                (w, U, upd_vec, upd_cnt, upd_ks, upd_kvec, ovf_vec,
                 ovf_at, ovf_cnt, ovf_ks, ovf_kvec, ovf_hwm, far_msgs,
                 err, op_census) = ops
                if dp_on:
                    nk = jax.random.fold_in(noise_base, t)
                    noised, _ = cohort_clip_noise(
                        U, nk, eta * done.astype(jnp.float32), done,
                        clip=dp_round_clip, noise_scale=noise_scale,
                        use_kernel=use_dp_kernel, interpret=interpret,
                        in_kernel_rng=in_kernel_rng, mesh=mesh)
                    sent = noised
                else:
                    sent = U
                # update latency addressed by (client, round) — st.i is
                # pre-increment, matching the host engine's draw point
                arr_off = plan.update_ticks(st.i)                  # [C]
                arr_slot = (t + arr_off) & (L - 1)
                # offsets past the ring go to the overflow bucket; the
                # ring (and its unrolled scatter) stays bounded by the
                # plan's ring_ticks, not the latency tail
                near = done & (arr_off < L) if F > 0 else done
                # ring scatter + DP w-consistency (Algorithm 1 line 24)
                # + U reset in ONE fused kernel call.  The per-row
                # masks / eta weights are the engines' historical
                # expressions precomputed as operands; the kernel's
                # reference path keeps each slot the host engine's
                # _weighted_sum over the full client axis under the
                # guarded add (rows with no arrivals stay bitwise
                # untouched — not old + 0), so host<->device bit parity
                # is unchanged.  FedAsync stratifies by the sender's
                # freshest-seen k (mod R): its [L, R, D] bucket
                # flattens to L*R scatter rows (sl-major, matching the
                # host's _make_strat_insert row loop).
                kmod = k & (R - 1) if stratified else None
                in_ls = [near & (arr_slot == sl) for sl in range(L)]
                if stratified:
                    masks = [in_l & (kmod == r)
                             for in_l in in_ls for r in range(R)]
                    rows = upd_kvec.reshape((L * R, D))
                else:
                    masks = in_ls
                    rows = upd_vec
                # distinct near slots scattered
                ring_sc = jnp.sum(jnp.stack(
                    [jnp.any(in_l) for in_l in in_ls]).astype(jnp.int32))
                wgt = jnp.stack([eta * m.astype(jnp.float32)
                                 for m in masks])                  # [G, C]
                any_g = jnp.stack([jnp.any(m) for m in masks])     # [G]
                w, U, rows = tick_scatter(sent, w, U, rows, wgt,
                                          any_g, done, eta, dp_on=dp_on,
                                          mesh=mesh)
                if stratified:
                    upd_kvec = rows.reshape((L, R, D))
                else:
                    upd_vec = rows
                oh_l = ((arr_slot[:, None] == jnp.arange(L)[None, :])
                        & near[:, None]).astype(jnp.int32)         # [C, L]
                oh_r = ((st.i & (R - 1))[:, None]
                        == jnp.arange(R)[None, :]).astype(jnp.int32)
                upd_cnt = upd_cnt + jnp.einsum("cl,cr->lr", oh_l, oh_r)
                # sender-k census ring, same layout keyed by the k each
                # finishing client saw at send (k is post-delivery for
                # this tick — the host engine reads st.k[c] at the same
                # point in its _finish_rounds)
                oh_s = ((k & (R - 1))[:, None]
                        == jnp.arange(R)[None, :]).astype(jnp.int32)
                upd_ks = upd_ks + jnp.einsum("cl,cr->lr", oh_l, oh_s)
                op_census = op_census.at[OP_RING_SCATTERS].add(ring_sc)
                if F > 0:
                    far_mask = done & (arr_off >= L)
                    arr_tick = t + arr_off
                    far_msgs = far_msgs + jnp.sum(
                        far_mask.astype(jnp.int32))
                    # do_far runs iff any(far_mask): counting its branch
                    # hit here (inside do_complete) is equivalent
                    op_census = op_census.at[OP_FAR_TICKS].add(
                        jnp.any(far_mask).astype(jnp.int32))

                    def do_far(fops):
                        (ovf_vec, ovf_at, ovf_cnt, ovf_ks, ovf_kvec,
                         ovf_hwm, err, op_census) = fops
                        far_grps = jnp.int32(0)
                        remaining = far_mask
                        # one unroll step per DISTINCT far arrival tick,
                        # ascending (matches the host's np.unique order);
                        # F = |{quantized bin values >= L}| bounds the
                        # distinct far ticks one completion can produce
                        for _ in range(F):
                            tick_q = jnp.min(jnp.where(
                                remaining, arr_tick,
                                jnp.int32(2 ** 31 - 1)))
                            grp = remaining & (arr_tick == tick_q)
                            any_grp = jnp.any(grp)
                            far_grps = far_grps + any_grp.astype(jnp.int32)
                            vec = jnp.sum(
                                sent * (eta
                                        * grp.astype(jnp.float32))[:, None],
                                axis=0)
                            cnt = jnp.sum(
                                oh_r * grp.astype(jnp.int32)[:, None],
                                axis=0)
                            cnt_ks = jnp.sum(
                                oh_s * grp.astype(jnp.int32)[:, None],
                                axis=0)
                            match = ovf_at == tick_q
                            has_match = jnp.any(match)
                            free = ovf_at == 0
                            ok = has_match | jnp.any(free)
                            idx = jnp.where(has_match, jnp.argmax(match),
                                            jnp.argmax(free))
                            write = any_grp & ok
                            if stratified:
                                # sender-k-stratified twin insert — the
                                # host runs _make_strat_insert on the
                                # same far bucket; guard per stratum so
                                # empty rows stay bitwise untouched
                                for r in range(R):
                                    grp_r = grp & (kmod == r)
                                    vec_r = jnp.sum(
                                        sent * (eta * grp_r.astype(
                                            jnp.float32))[:, None],
                                        axis=0)
                                    ovf_kvec = ovf_kvec.at[idx, r].set(
                                        jnp.where(
                                            write & jnp.any(grp_r),
                                            ovf_kvec[idx, r] + vec_r,
                                            ovf_kvec[idx, r]))
                            else:
                                ovf_vec = ovf_vec.at[idx].set(
                                    jnp.where(write, ovf_vec[idx] + vec,
                                              ovf_vec[idx]))
                            ovf_cnt = ovf_cnt.at[idx].set(
                                jnp.where(write, ovf_cnt[idx] + cnt,
                                          ovf_cnt[idx]))
                            ovf_ks = ovf_ks.at[idx].set(
                                jnp.where(write, ovf_ks[idx] + cnt_ks,
                                          ovf_ks[idx]))
                            ovf_at = ovf_at.at[idx].set(
                                jnp.where(write, tick_q, ovf_at[idx]))
                            err = err | (any_grp & ~ok).astype(jnp.int32)
                            remaining = remaining & ~grp
                        err = err | jnp.any(remaining).astype(jnp.int32)
                        # occupancy high-water mark, sampled after this
                        # tick's inserts — one occupied slot per pending
                        # far arrival tick, the host engine's
                        # len(far_contrib) at the same point
                        ovf_hwm = jnp.maximum(
                            ovf_hwm,
                            jnp.sum((ovf_at != 0).astype(jnp.int32)))
                        op_census = op_census.at[OP_FAR_GROUPS].add(
                            far_grps)
                        return (ovf_vec, ovf_at, ovf_cnt, ovf_ks,
                                ovf_kvec, ovf_hwm, err, op_census)

                    (ovf_vec, ovf_at, ovf_cnt, ovf_ks, ovf_kvec,
                     ovf_hwm, err, op_census) = lax.cond(
                        jnp.any(far_mask), do_far, lambda fops: fops,
                        (ovf_vec, ovf_at, ovf_cnt, ovf_ks, ovf_kvec,
                         ovf_hwm, err, op_census))
                return (w, U, upd_vec, upd_cnt, upd_ks, upd_kvec,
                        ovf_vec, ovf_at, ovf_cnt, ovf_ks, ovf_kvec,
                        ovf_hwm, far_msgs, err, op_census)

            (w, U, upd_vec, upd_cnt, upd_ks, upd_kvec, ovf_vec, ovf_at,
             ovf_cnt, ovf_ks, ovf_kvec, ovf_hwm, far_msgs, err,
             op_census) = lax.cond(
                any_done, do_complete, lambda ops: ops,
                (w, U, upd_vec, upd_cnt, upd_ks, upd_kvec, ovf_vec,
                 ovf_at, ovf_cnt, ovf_ks, ovf_kvec, st.ovf_hwm,
                 st.far_msgs, st.err, op_census))
            i = jnp.where(done, st.i + 1, st.i)
            h = jnp.where(done, 0, h)
            credit = jnp.where(
                done, jnp.minimum(credit, block << FRAC_BITS), credit)

            return DeviceCohortState(
                w=w, U=U, v=v, i=i, h=h, k=k, credit=credit,
                server_k=server_k, tick=t, upd_vec=upd_vec,
                upd_cnt=upd_cnt, h_counts=h_counts, bc_v=bc_v,
                bc_k=bc_k, bc_at=bc_at, ovf_vec=ovf_vec, ovf_at=ovf_at,
                ovf_cnt=ovf_cnt, err=err, messages=messages,
                broadcasts=broadcasts, part=part, bytes_up=bytes_up,
                stale_hist=stale_hist, upd_ks=upd_ks, ovf_ks=ovf_ks,
                ovf_hwm=ovf_hwm, far_msgs=far_msgs, upd_kvec=upd_kvec,
                ovf_kvec=ovf_kvec, buf_vec=buf_vec, buf_cnt=buf_cnt,
                ops=op_census, iters=st.iters)

        def predict_block(s):
            """Int-only preview of tick s.tick + 1's block predicate.

            Mirrors the deliver-k advance and credit accrual on the
            PRE-tick broadcast state; a cascade fired by the next tick
            itself (same-tick delivery) can make this wrong, which only
            shifts which iteration a block tick lands in — the merged
            tick is the full tick_fn, so the protocol state, the ops
            census, and the relations block_iters <= loop_iters <=
            ticks are exact regardless.
            """
            T = s.tick + 1
            elig2 = (s.bc_at <= T) & (s.bc_k[:, None] > s.k[None, :])
            best_k2 = jnp.max(jnp.where(elig2, s.bc_k[:, None], 0),
                              axis=0)
            k2 = jnp.where(best_k2 > s.k, best_k2, s.k)
            active2 = s.i < k2 + d_gate
            if avail_mask is not None:
                active2 = active2 & avail_mask(T)
            credit2 = s.credit + jnp.where(active2, accrual, 0)
            s_i2 = sizes[cidx, jnp.minimum(s.i, sizes.shape[1] - 1)]
            n2 = jnp.where(active2,
                           jnp.minimum(s_i2 - s.h,
                                       credit2 >> FRAC_BITS), 0)
            return jnp.any(jnp.maximum(n2, 0) > 0)

        def loop_body(st0: DeviceCohortState) -> DeviceCohortState:
            # tick coalescing (fuse_ticks): run the tick, and when the
            # NEXT tick (a) would run under the loop condition anyway
            # and (b) is predicted to do no client compute, run it in
            # the same while_loop iteration.  The merged tick is the
            # SAME tick_fn under the same condition the unfused loop
            # would have evaluated, so the tick sequence — and every
            # protocol/census counter — is identical bitwise; only the
            # iteration attribution in ``iters`` changes.  Overhead-only
            # ticks thus ride along with compute iterations instead of
            # costing a loop step of their own.
            st1 = tick_fn(st0)
            if fuse_ticks:
                merge = ((st1.server_k < target_k)
                         & (st1.tick < tick_limit) & (st1.err == 0)
                         & ~predict_block(st1))
                st2 = lax.cond(merge, tick_fn, lambda s: s, st1)
            else:
                st2 = st1
            had_block = (st2.ops[OP_BLOCK_TICKS]
                         > st0.ops[OP_BLOCK_TICKS]).astype(jnp.int32)
            return st2._replace(
                iters=st0.iters + jnp.stack([jnp.int32(1), had_block]))

        return lax.while_loop(
            lambda s: ((s.server_k < target_k) & (s.tick < tick_limit)
                       & (s.err == 0)),
            loop_body, st)

    return jax.jit(segment, donate_argnums=(0,))


class DeviceCohortEngine:
    """Drop-in engine with the ``CohortEngine`` constructor vocabulary,
    minus host-callable latency (see module docstring)."""

    def __init__(self, ctask, *, sizes_per_client,
                 round_stepsizes: Sequence[float], d: int = 1,
                 speeds: Optional[Sequence[float]] = None,
                 latency=None, seed: int = 0, block: int = 64,
                 dp_sigma: float = 0.0, dp_clip: float = 0.0,
                 dp_round_clip: float = 0.0, use_dp_kernel: bool = True,
                 interpret: Optional[bool] = None, scenario=None,
                 trace=None, dp_delta: float = 1e-5, strategy=None,
                 dp_rng: str = "operand", fuse_ticks: bool = True):
        self.ctask = ctask
        C = ctask.C
        self.C = C
        self.D = ctask.D
        self.d_gate = int(d)
        self.block = int(block)
        if (2 * self.block) << FRAC_BITS >= 2 ** 31:
            raise ValueError(
                f"block={block} overflows the device engine's int32 "
                f"fixed-point credit (max {(2 ** 30 >> FRAC_BITS) - 1}); "
                "use the host cohort engine for larger blocks")
        self.seed = int(seed)
        if scenario is not None and latency is not None:
            raise ValueError("pass either scenario= or latency=, not both")
        scn = (get_scenario(scenario) if scenario is not None
               else legacy_latency_scenario(latency))
        if speeds is None:
            speeds = scn.speeds(C, seed)
        self.speeds = np.asarray(speeds if speeds is not None
                                 else np.ones(C), np.float64)
        assert len(self.speeds) == C
        self.dt = self.block / float(self.speeds.max())
        self._plan = scenario_plan(scn, C=C, seed=self.seed, dt=self.dt)

        self.sizes = pad_sizes(sizes_per_client, C)
        self.etas = np.asarray(round_stepsizes, np.float64)

        from repro.core.tasks import validate_dp_knobs
        validate_dp_knobs(dp_clip, dp_sigma, "DeviceCohortEngine")
        self.dp_sigma = float(dp_sigma)
        self.dp_clip = float(dp_clip)
        self.dp_round_clip = float(dp_round_clip)
        self.use_dp_kernel = bool(use_dp_kernel)
        # interpret=None: infer from the backend — interpret-mode Pallas
        # on CPU (byte-identical to the historical default there), the
        # compiled kernel on a real TPU/GPU
        self.interpret = ((jax.default_backend() == "cpu")
                          if interpret is None else bool(interpret))
        # DP noise source: "operand" streams jax.random normals into the
        # clip+noise kernel (bitwise host-vs-device, the parity/golden
        # contract); "in_kernel" draws via pltpu.prng_random_bits inside
        # the kernel (TPU only — no HBM noise block, distributionally
        # equivalent, pinned by a chi-square test instead of bitwise)
        if dp_rng not in ("operand", "in_kernel"):
            raise ValueError(f"dp_rng={dp_rng!r} not in "
                             f"('operand', 'in_kernel')")
        if dp_rng == "in_kernel":
            if jax.default_backend() != "tpu":
                raise ValueError(
                    "dp_rng='in_kernel' needs a TPU backend: the "
                    "pltpu.prng_random_bits kernel has no CPU/GPU "
                    "lowering (use dp_rng='operand')")
            if not self.use_dp_kernel:
                raise ValueError("dp_rng='in_kernel' requires "
                                 "use_dp_kernel=True")
        self.dp_rng = dp_rng
        self.fuse_ticks = bool(fuse_ticks)
        self.dp_delta = float(dp_delta)
        self._trace = open_trace(trace)

        # ring capacities and the static per-tick block size: n is bounded
        # by the round size AND by the credit cap (2 * block post-accrual).
        # L covers the latency table's tail only up to the plan's
        # ring boundary (Scenario.ring_cap): draws quantizing past it go
        # to the Q-slot overflow bucket instead of widening the ring and
        # its unrolled scatter, so compile time/memory no longer scale
        # with next_pow2(max latency ticks) under heavy-tailed tables.
        # F bounds the distinct far arrival ticks one completion tick
        # can produce (the count of quantized bin values past the ring),
        # itself capped at FAR_UNROLL_CAP so a fine-binned per-client
        # table union cannot reintroduce tail-scaling compile cost —
        # a completion tick needing more far groups than the unroll
        # covers trips the err latch (raise ring_cap) instead.
        self.L = self._plan.ring_ticks
        self.F = min(len(self._plan.far_tick_values), FAR_UNROLL_CAP)
        self.Q = (next_pow2(min(C * (self.d_gate + 1),
                                self._plan.max_lat_ticks + 1, 128))
                  if self.F else 1)
        self.R = next_pow2(self.d_gate + 2)
        self.B = next_pow2(self.d_gate + 2)
        self.strategy = get_strategy(strategy)
        self.b_stat = next_pow2(
            max(1, min(2 * self.block, int(self.sizes.max()))))

        self.mesh = cohort_mesh()
        self._shardings = cohort_shardings(self.mesh, C)
        # the fused client kernels need the mesh only when C is sharded
        self._kernel_mesh = (self.mesh if self._shardings["w"].spec[0]
                             else None)
        self.state = self._init_state()
        # replicated operands are committed to every device of the mesh:
        # a steady segment may not move them (transfer guard below)
        self._replicated = NamedSharding(self.mesh, PartitionSpec())
        self._etas_dev = jax.device_put(
            np.asarray(self.etas, np.float32), self._replicated)
        self._sizes_dev = jax.device_put(
            jnp.asarray(self.sizes, jnp.int32), self._shardings["w"])
        self._accrual_dev = jax.device_put(
            jnp.asarray(speed_accrual(self.speeds, self.block), jnp.int32),
            self._shardings["credit"])
        self.history: List[Dict[str, float]] = []

    def _init_state(self) -> DeviceCohortState:
        C, D, L, R, B, Q = self.C, self.D, self.L, self.R, self.B, self.Q
        v0 = jnp.asarray(self.ctask.init_flat(), jnp.float32)
        # four distinct buffers — donation rejects aliased arguments
        zc = lambda: jnp.zeros((C,), jnp.int32)  # noqa: E731
        fields = dict(
            w=jnp.tile(v0[None, :], (C, 1)),
            U=jnp.zeros((C, D), jnp.float32),
            v=v0, i=zc(), h=zc(), k=zc(), credit=zc(),
            server_k=jnp.int32(0), tick=jnp.int32(0),
            upd_vec=jnp.zeros((L, D), jnp.float32),
            upd_cnt=jnp.zeros((L, R), jnp.int32),
            h_counts=jnp.zeros((R,), jnp.int32),
            bc_v=jnp.zeros((B, D), jnp.float32),
            bc_k=jnp.zeros((B,), jnp.int32),
            bc_at=jnp.zeros((B, C), jnp.int32),
            ovf_vec=jnp.zeros((Q, D), jnp.float32),
            ovf_at=jnp.zeros((Q,), jnp.int32),
            ovf_cnt=jnp.zeros((Q, R), jnp.int32),
            err=jnp.int32(0),
            messages=jnp.int32(0), broadcasts=jnp.int32(0),
            part=zc(), bytes_up=zc(),
            stale_hist=jnp.zeros((STALE_BINS,), jnp.int32),
            upd_ks=jnp.zeros((L, R), jnp.int32),
            ovf_ks=jnp.zeros((Q, R), jnp.int32),
            ovf_hwm=jnp.int32(0), far_msgs=jnp.int32(0),
            # aggregation-strategy buffers: full-size only when the
            # strategy uses them ([1, ...] dummies otherwise keep the
            # donated state pytree small under the paper default)
            upd_kvec=jnp.zeros((L, R, D) if self.strategy.stratified
                               else (1, 1, 1), jnp.float32),
            ovf_kvec=jnp.zeros((Q, R, D) if self.strategy.stratified
                               else (1, 1, 1), jnp.float32),
            buf_vec=jnp.zeros((D,) if self.strategy.buffered else (1,),
                              jnp.float32),
            buf_cnt=jnp.int32(0),
            ops=jnp.zeros((N_OPS,), jnp.int32),
            iters=jnp.zeros((2,), jnp.int32))
        return DeviceCohortState(**{
            f: jax.device_put(val, self._shardings[f])
            for f, val in fields.items()})

    # -- compiled segment (cached on the cohort task, like its block fns) --
    def _segment_fn(self):
        key = ("device_segment", self.C, self.D, self.block, self.b_stat,
               self.d_gate, self.L, self.R, self.B, self.Q,
               self._plan.fingerprint(), self.dp_clip, self.dp_sigma,
               self.dp_round_clip, self.use_dp_kernel, self.interpret,
               self.dp_rng, self.fuse_ticks, self.seed,
               self.strategy.fingerprint(), self._kernel_mesh)
        cache = getattr(self.ctask, "_segment_fns", None)
        if cache is None:
            cache = self.ctask._segment_fns = {}
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = _build_segment(
                self.ctask, C=self.C, D=self.D, block=self.block,
                b_stat=self.b_stat, d_gate=self.d_gate, L=self.L,
                R=self.R, B=self.B, Q=self.Q, F=self.F,
                plan=self._plan, dp_clip=self.dp_clip,
                dp_sigma=self.dp_sigma, dp_round_clip=self.dp_round_clip,
                use_dp_kernel=self.use_dp_kernel,
                interpret=self.interpret,
                in_kernel_rng=(self.dp_rng == "in_kernel"),
                fuse_ticks=self.fuse_ticks, seed=self.seed,
                strategy=self.strategy, mesh=self._kernel_mesh)
        return fn

    @property
    def fused_iters(self):
        """(loop_iters, block_iters): while_loop iterations executed and
        how many contained a block tick — the tick-coalescing census the
        bench's ``tick_overhead_ratio`` is computed from (syncs)."""
        it = np.asarray(self.state.iters)
        return int(it[0]), int(it[1])

    @property
    def total_messages(self) -> int:
        return int(self.state.messages)

    @property
    def total_broadcasts(self) -> int:
        return int(self.state.broadcasts)

    # -- main loop ----------------------------------------------------------
    def run(self, *, max_rounds: int, eval_every: int = 1,
            eval_fn: Optional[Callable] = None,
            max_ticks: Optional[int] = None) -> Dict[str, Any]:
        """Run until the server completes ``max_rounds`` broadcasts.

        Same result schema as ``AsyncFLSimulator.run`` /
        ``CohortEngine.run``; the device is synced once per eval segment.
        """
        if eval_fn is not None:
            evals = lambda vec: eval_fn(self.ctask.unflatten(vec))  # noqa: E731
        else:
            evals = self.ctask.metrics
        if max_ticks is None:
            max_ticks = default_max_ticks(
                self.sizes, self.speeds, self.block, max_rounds,
                lat_tail_ticks=self._plan.max_lat_ticks,
                duty=self._plan.duty)
        seg = self._segment_fn()
        st = self.state
        next_eval = eval_every
        # kept on the engine so the timeline CLI (python -m
        # repro.telemetry capture) can export the wall spans after run()
        timer = self.timer = PhaseTimer()
        first_segment = True
        while True:
            target = min(next_eval, max_rounds)
            # scalar segment bounds are committed to device OUTSIDE the
            # transfer guard below — the guarded steady dispatch must
            # see device-resident operands only
            tgt = jax.device_put(np.int32(target), self._replicated)
            lim = jax.device_put(np.int32(max_ticks), self._replicated)
            with timer.phase("first_segment" if first_segment
                             else "steady"):
                if first_segment:
                    # compile + closure-constant upload happen here
                    st = seg(st, self._etas_dev, self._sizes_dev,
                             self._accrual_dev, tgt, lim)
                else:
                    # runtime sanitizer (parity contract): a steady
                    # segment performs ZERO implicit host<->device
                    # transfers between eval syncs — a hidden transfer
                    # raises here instead of silently serializing the
                    # jitted tick loop
                    with jax.transfer_guard("disallow"):
                        st = seg(st, self._etas_dev, self._sizes_dev,
                                 self._accrual_dev, tgt, lim)
                self.state = st
                sk = int(st.server_k)        # the one sync per segment
                # phase-accurate timing: the while_loop's outputs
                # materialize together, but make the boundary explicit
                # so async dispatch can never charge segment work to
                # the eval phase that follows
                jax.block_until_ready(st.v)
            first_segment = False
            if sk < target:
                if int(st.err) != 0:
                    raise RuntimeError(
                        f"device engine overflow bucket exhausted at "
                        f"tick {int(st.tick)} (Q={self.Q} slots, "
                        f"F={self.F} far groups/tick, ring L={self.L}):"
                        f" too many distinct far arrival ticks in "
                        f"flight — raise Scenario.ring_cap (now "
                        f"{self._plan.scenario.ring_cap}) or shorten "
                        f"the latency tail")
                raise RuntimeError(
                    f"cohort engine stalled: {int(st.tick)} ticks, "
                    f"server_k={sk} < {max_rounds} "
                    f"(in flight: "
                    f"{int(jnp.sum(st.upd_cnt)) + int(jnp.sum(st.ovf_cnt))}"
                    f" updates, "
                    f"{int(jnp.sum(jnp.any(st.bc_at > st.tick, axis=1)))}"
                    f" broadcasts)")
            if sk >= next_eval:
                with timer.phase("eval"):
                    m = evals(st.v)
                    m.update(round=sk, time=int(st.tick) * self.dt,
                             messages=int(st.messages))
                    self.history.append(m)
                    next_eval = sk + eval_every
                    self._emit_segment()
            if sk >= max_rounds:
                break
        with timer.phase("eval"):
            final = evals(st.v)
        # overflow telemetry surfaced for ring_cap tuning: the high-water
        # mark against the Q-slot capacity plus the far-routed share
        final.update(round=sk, time=int(st.tick) * self.dt,
                     messages=int(st.messages),
                     broadcasts=int(st.broadcasts),
                     overflow_hwm=int(st.ovf_hwm),
                     overflow_slots=self.Q if self.F else 0,
                     far_messages=int(st.far_msgs))
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
            "segment", engine="device", round=int(st.server_k),
            tick=int(st.tick), time=int(st.tick) * self.dt,
            messages=int(st.messages),
            broadcasts=int(st.broadcasts),
            bytes_up_total=int(np.asarray(st.bytes_up,
                                          dtype=np.int64).sum()),
            staleness_hist=np.asarray(st.stale_hist),
            overflow_hwm=int(st.ovf_hwm),
            ops=np.asarray(st.ops))

    def telemetry_report(self, wall=None):
        """MetricsReport from the on-device counters (syncs the state)."""
        st = self.state
        src_task = getattr(self.ctask, "task", None)
        return build_report(
            engine="device", clients=self.C, flat_dim=self.D,
            rounds=int(st.server_k), messages=int(st.messages),
            broadcasts=int(st.broadcasts),
            participation=np.asarray(st.part, dtype=np.int64),
            bytes_up=np.asarray(st.bytes_up, dtype=np.int64),
            staleness_hist=np.asarray(st.stale_hist, dtype=np.int64),
            overflow_hwm=int(st.ovf_hwm),
            overflow_slots=self.Q if self.F else 0,
            far_messages=int(st.far_msgs),
            ticks=int(st.tick),
            ops=np.asarray(st.ops, dtype=np.int64),
            dp_sigma=self.dp_sigma, dp_delta=self.dp_delta,
            n_examples=(int(src_task.X.shape[0])
                        if hasattr(src_task, "X") else None),
            sizes_per_client=self.sizes, wall=wall)
