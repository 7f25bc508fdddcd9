"""Differential-privacy accountant — Theorems 3, 4, 6 of the paper.

The paper generalizes the moments accountant of Abadi et al. (2016) to
*increasing* sample-size sequences q_i = s_{i,c}/N_c = q (i+m)^p and makes
the constants explicit.  This module implements:

  * ``r_from_r0``          — equation (16): r(r0, σ)
  * ``r0_sigma``           — the fixed-point iteration for r0(σ) (D.3.1)
  * ``Theorem4Constants``  — A, B, D, K−, K+, K*, ρ, ρ̂ (γ/α-corrected,
                              i.e. the full Theorem 6 forms)
  * ``sigma_lower_bound``  — case-1 and case-2 σ bounds
  * ``select_parameters``  — the iterative parameter-selection procedure of
                              §3 / D.3.2 (reproduces Examples 1–5)
  * ``moments_epsilon``    — a *numerical* accountant from Lemma 4's explicit
                              moment bound: works for arbitrary {s_i}, used
                              to cross-check the closed forms.
  * ``keys_epsilon``       — the same accountant over many size rows at
                              once (the per-client telemetry rows), equal to
                              ``moments_epsilon`` row by row.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

E = math.e
SQRT3M1_HALF = (math.sqrt(3.0) - 1.0) / 2.0


# ---------------------------------------------------------------------------
# r(r0, sigma) — equation (16)
# ---------------------------------------------------------------------------

def u0_u1(r0: float, sigma: float):
    if not 0.0 < r0 < sigma:
        # With r0 >= sigma the denominator sigma - r0 flips sign, u0/u1 go
        # negative, the < 1 guard in r_from_r0 passes vacuously, and a
        # finite but meaningless r leaks into Theorem4Constants /
        # select_parameters.  Equation (16) is only defined on 0 < r0 < σ.
        raise ValueError(
            f"equation (16) requires 0 < r0 < sigma; got r0={r0}, "
            f"sigma={sigma}")
    root = math.sqrt(r0 * sigma)
    u0 = 2.0 * root / (sigma - r0)
    u1 = 2.0 * E * root / ((sigma - r0) * sigma)
    return u0, u1


def r_from_r0(r0: float, sigma: float) -> float:
    u0, u1 = u0_u1(r0, sigma)
    if u0 >= 1.0 or u1 >= 1.0:
        raise ValueError(f"u0={u0:.4f}, u1={u1:.4f} must be < 1 "
                         f"(sigma too small for r0={r0})")
    return r0 * 8.0 * (1.0 / (1.0 - u0)
                       + (1.0 / (1.0 - u1)) * E ** 3 / sigma ** 3) \
        * math.exp(3.0 / sigma ** 2)


def r0_sigma(sigma: float, p: float = 1.0, *, tol: float = 1e-12,
             max_iter: int = 200) -> float:
    """Fixed point r0(σ) from D.3.1 (requires σ >= 1.137).

    Solves  r(r0, σ) = (√3−1)/2 · (3p+1)/((p+1)(2p+1)) · (1 − r0/σ)².
    """
    if sigma < 1.137:
        raise ValueError("r0(sigma) iteration requires sigma >= 1.137")
    target_coef = SQRT3M1_HALF * (3 * p + 1) / ((p + 1) * (2 * p + 1))
    r0 = 0.0
    for _ in range(max_iter):
        num = target_coef * (1.0 - r0 / sigma) ** 2
        u0, u1 = u0_u1(r0, sigma) if r0 > 0 else (0.0, 0.0)
        den = 8.0 * (1.0 / (1.0 - u0)
                     + (1.0 / (1.0 - u1)) * E ** 3 / sigma ** 3) \
            * math.exp(3.0 / sigma ** 2)
        new = num / den
        if abs(new - r0) < tol:
            return new
        r0 = new
    return r0


# ---------------------------------------------------------------------------
# Theorem 6 constants (γ, α corrected)
# ---------------------------------------------------------------------------

@dataclass
class Theorem4Constants:
    p: float
    r0: float
    sigma: float
    gamma: float = 0.0       # m/T
    alpha: Optional[float] = None

    def __post_init__(self):
        if self.alpha is None:
            self.alpha = self.r0 / self.sigma
        self.r = r_from_r0(self.r0, self.sigma)
        p, g, a = self.p, self.gamma, self.alpha
        self.rho = ((2 * p + 1) ** 2 / ((p + 1) * (3 * p + 1))
                    * (1 + g) ** (2 + 4 * p) / (1 - a) ** 2)
        self.rho_hat = (2 * p + 1) / (p + 1) ** 2 * (1 + g) ** (2 + 2 * p)
        rr = self.r * self.rho
        # equation (24): threshold τ on c1
        self.tau = (((2 * rr / self.rho_hat + 1.0) ** 2 - 1.0)
                    / (2.0 * rr))

    # -- A, B, D coefficients ------------------------------------------------
    @property
    def A(self) -> float:
        p, g = self.p, self.gamma
        return ((p + 1) ** (1.0 / (1 + 2 * p))
                / (1.0 / (self.r * self.rho)) ** ((1 + p) / (1 + 2 * p))
                * (1 + g) ** (1 + p))

    @property
    def B(self) -> float:
        p, g = self.p, self.gamma
        return ((1 + g) ** (-2.0 * (1 + p) ** 2 / (1 + 2 * p))
                * (p + 1) ** (1.0 / (1 + 2 * p))
                / self.tau ** ((1 + p) / (1 + 2 * p)))

    @property
    def D(self) -> float:
        p, g = self.p, self.gamma
        if p <= 0:
            return math.inf
        return ((self.r0 / self.sigma) ** ((1 + p) / p) / (p + 1)
                * (1 + g) ** (1 + p))

    # -- thresholds ------------------------------------------------------------
    def K_minus(self, epsilon: float, q: float, N_c: int) -> float:
        p = self.p
        return (self.B * epsilon ** ((1 + p) / (1 + 2 * p))
                * q ** (-1.0 / (1 + 2 * p)) * N_c)

    def K_plus(self, epsilon: float, q: float, N_c: int) -> float:
        p = self.p
        return (self.A * epsilon ** ((1 + p) / (1 + 2 * p))
                * q ** (-1.0 / (1 + 2 * p)) * N_c)

    def K_star(self, q: float, N_c: int) -> float:
        if self.p <= 0:
            return math.inf
        return self.D * q ** (-1.0 / self.p) * N_c


def theorem4_simple_B(p: float) -> float:
    """Theorem 4's headline B = (1/(1+p)) ((√3−1)/2 (2p+1))^{(1+p)/(1+2p)}
    (the r0(σ) fixed-point value, γ = 0)."""
    return (1.0 / (1 + p)) * (SQRT3M1_HALF * (2 * p + 1)) \
        ** ((1 + p) / (1 + 2 * p))


# ---------------------------------------------------------------------------
# σ lower bounds
# ---------------------------------------------------------------------------

def privacy_budget_B(epsilon: float, delta: float) -> float:
    return math.sqrt(2.0 * math.log(1.0 / delta) / epsilon)


def delta_from_budget(B: float, epsilon: float) -> float:
    return math.exp(-B * B * epsilon / 2.0)


def sigma_lower_bound_case1(epsilon: float, delta: float, *, p: float,
                            r0: float, sigma: float,
                            gamma: float = 0.0) -> float:
    """Case 1 (K <= K−): σ ≥ √(2 ln(1/δ)/ε) (1+γ)^{2+3p} / √(1 − r0/σ)."""
    return (privacy_budget_B(epsilon, delta)
            * (1 + gamma) ** (2 + 3 * p)
            / math.sqrt(1.0 - r0 / sigma))


def sigma_lower_bound_case2(epsilon: float, delta: float, *, p: float,
                            r0: float, sigma: float, K: float, K_plus: float,
                            gamma: float = 0.0) -> float:
    """Case 2 (K >= K+): the 1.21 · (K/K+)^{(1+2p)/(2+2p)} bound (eq 19)."""
    return ((K / K_plus) ** ((1 + 2 * p) / (2 + 2 * p)) * 1.21
            * privacy_budget_B(epsilon, delta)
            * (1 + gamma) ** (2 + 3 * p)
            / math.sqrt(1.0 - r0 / sigma))


# ---------------------------------------------------------------------------
# Parameter-selection procedure (§3 "Parameter selection", D.3.2)
# ---------------------------------------------------------------------------

@dataclass
class SelectedParameters:
    q: float
    m: float
    T: int
    gamma: float
    sigma: float
    r0: float
    epsilon: float
    delta: float
    budget_B: float
    K: int
    sizes: List[int]
    T_constant: int
    round_reduction: float
    aggregated_noise: float           # sqrt(T) * sigma
    aggregated_noise_constant: float  # sqrt(T_const) * B  (fair comparison)
    binding: str                      # which constraint bound q

    def summary(self) -> str:
        return (f"q={self.q:.3e} m={self.m:.2f} T={self.T} "
                f"gamma={self.gamma:.4f} sigma={self.sigma} "
                f"B={self.budget_B:.3f} delta={self.delta:.3e} "
                f"rounds {self.T_constant}->{self.T} "
                f"(x{self.round_reduction:.2f} fewer), noise "
                f"{self.aggregated_noise_constant:.0f}->"
                f"{self.aggregated_noise:.0f}")


def select_parameters(*, s0c: int, N_c: int, p: float, epsilon: float,
                      sigma: float, K: int, r0: Optional[float] = None,
                      n_gamma_iters: int = 6) -> SelectedParameters:
    """Case-1 selection: choose q ≤ min(q(K−), q(K*)), derive m, T, γ,
    iterate γ to a fixed point, then read off the achievable budget B/δ.

    ``r0=None`` uses the r0(σ) fixed point; Examples 3/5 of the paper use
    r0 = 1/e to relax the K* constraint — pass r0=1/e to reproduce them.
    """
    r0v = r0_sigma(sigma, p) if r0 is None else r0
    gamma = 0.0
    q = m = T = None
    binding = "?"
    for _ in range(n_gamma_iters):
        consts = Theorem4Constants(p=p, r0=r0v, sigma=sigma, gamma=gamma)
        # q small enough that K <= K−  =>  q <= (B ε^{(1+p)/(1+2p)} N_c/K)^{1+2p}
        q_kminus = (consts.B * epsilon ** ((1 + p) / (1 + 2 * p))
                    * N_c / K) ** (1 + 2 * p)
        # q small enough that K <= K*  =>  q <= (D N_c / K)^{p}
        q_kstar = (consts.D * N_c / K) ** p if p > 0 else math.inf
        if q_kminus <= q_kstar:
            q, binding = q_kminus, "K-"
        else:
            q, binding = q_kstar, "K*"
        m = (s0c / (N_c * q)) ** (1.0 / p) if p > 0 else 0.0
        s = N_c * q * (m ** p) if p > 0 else s0c   # = s0c by construction
        T = ((p + 1) * K / (N_c * q)) ** (1.0 / (1 + p))
        new_gamma = m / T
        if abs(new_gamma - gamma) < 1e-9:
            gamma = new_gamma
            break
        gamma = new_gamma

    T_int = int(round(T))
    bound_factor = (1 + gamma) ** (2 + 3 * p) / math.sqrt(1.0 - r0v / sigma)
    budget_B = sigma / bound_factor
    delta = delta_from_budget(budget_B, epsilon)

    sizes = [int(math.ceil(N_c * q * (i + m) ** p)) for i in range(T_int)]
    T_const = int(math.ceil(K / s0c))
    return SelectedParameters(
        q=q, m=m, T=T_int, gamma=gamma, sigma=sigma, r0=r0v,
        epsilon=epsilon, delta=delta, budget_B=budget_B, K=K, sizes=sizes,
        T_constant=T_const,
        round_reduction=T_const / max(T_int, 1),
        aggregated_noise=math.sqrt(T_int) * sigma,
        aggregated_noise_constant=math.sqrt(T_const) * budget_B,
        binding=binding)


# ---------------------------------------------------------------------------
# Numerical moments accountant (Lemma 4, explicit constants)
# ---------------------------------------------------------------------------

def moments_delta(sizes: Sequence[int], N_c: int, sigma: float,
                  epsilon: float, *, r0: Optional[float] = None,
                  lambda_max: int = 256) -> float:
    """δ = min_λ exp(Σ_i α_i(λ) − λ ε) using Lemma 4's bound

        α_i(λ) ≤ s²λ(λ+1)/(N(N−s)σ²) + (r/r0)·s³λ²(λ+1)/(N(N−s)²σ³).

    λ is capped by the lemma's validity condition λ ≤ σ² ln(N/(s σ)).
    """
    if r0 is None:
        r0 = max(s / N_c for s in sizes) * sigma
        r0 = min(max(r0, 1e-6), 1.0 / E)
    r = r_from_r0(r0, sigma)
    best = math.inf
    for lam in range(1, lambda_max + 1):
        ok = True
        total = 0.0
        for s in sizes:
            s = min(s, N_c - 1)
            if lam > sigma ** 2 * math.log(max(N_c / (s * sigma), E)):
                ok = False
                break
            t1 = s * s * lam * (lam + 1) / (N_c * (N_c - s) * sigma ** 2)
            t2 = (r / r0) * s ** 3 * lam ** 2 * (lam + 1) \
                / (N_c * (N_c - s) ** 2 * sigma ** 3)
            total += t1 + t2
        if not ok:
            break
        best = min(best, total - lam * epsilon)
    return math.exp(best) if best < math.inf else 1.0


def moments_epsilon(sizes: Sequence[int], N_c: int, sigma: float,
                    delta: float, *, r0: Optional[float] = None,
                    tol: float = 1e-4) -> float:
    """Smallest ε with moments_delta(...) <= δ (bisection)."""
    lo, hi = 1e-4, 200.0
    if moments_delta(sizes, N_c, sigma, hi, r0=r0) > delta:
        return math.inf
    for _ in range(80):
        mid = 0.5 * (lo + hi)
        if moments_delta(sizes, N_c, sigma, mid, r0=r0) <= delta:
            hi = mid
        else:
            lo = mid
        if hi - lo < tol:
            break
    return hi


# ---------------------------------------------------------------------------
# Per-client accounting (telemetry)
# ---------------------------------------------------------------------------

def participation_keys(schedules, participation: Sequence[int]):
    """The distinct (schedule, participation count) keys of a fleet.

    ``schedules`` is a [C, L] array or C sequences of any lengths: row c
    is client c's round schedule s_{i,c}, its last size repeating past
    its end (``Client.s``); client c sent ``participation[c]`` updates.
    Returns ``(table, counts, inverse)``: key k charges the first
    ``counts[k]`` sizes of ``table[k]`` ([K, W] int64, W >= every
    count), and client c holds key ``inverse[c]``.  A fleet on one
    schedule has one key per distinct count.
    """
    part = np.asarray(participation, np.int64)
    if isinstance(schedules, np.ndarray):
        tab = schedules.astype(np.int64, copy=False)
    else:
        lens = [len(s) for s in schedules]
        tab = np.zeros((len(lens), max([*lens, 1])), np.int64)
        for c, (s, n) in enumerate(zip(schedules, lens)):
            tab[c, :n] = s
            tab[c, n:] = s[-1] if n else 0
    if (tab == tab[:1]).all():       # one schedule: keys are the counts
        rows, row_of = tab[:1], np.zeros(len(tab), np.int64)
    else:
        rows, row_of = np.unique(tab, axis=0, return_inverse=True)
    span = int(part.max(initial=0)) + 1
    keys, inverse = np.unique(row_of.reshape(-1) * span + part,
                              return_inverse=True)
    row_k, counts = np.divmod(keys, span)
    L = rows.shape[1]
    width = max(int(counts.max(initial=0)), L)
    table = rows[row_k][:, np.minimum(np.arange(width), L - 1)]
    return table, counts, inverse.reshape(-1)


def keys_epsilon(table: np.ndarray, counts: np.ndarray, N_c: int,
                 sigma: float, delta: float, *, r0: Optional[float] = None
                 ) -> np.ndarray:
    """``moments_epsilon`` of every key at once ([K] float64).

    Key k is the first ``counts[k]`` sizes of ``table[k]``.  Equal, key
    by key, to ``moments_epsilon`` of that row: the same float64
    operations in the same order, on [keys, λ] arrays.  Only the sums
    Σ_i α_i(λ) do not depend on ε, so they are taken once (left to
    right over the rounds, each key with its own r0 default), not once
    a bisection step.  As in the accounting rows: an empty key costs 0;
    σ <= 0, or σ below Lemma 4's regime (u0/u1 >= 1), costs ∞.
    """
    tol, lambda_max = 1e-4, 256      # moments_epsilon's, moments_delta's
    counts = np.asarray(counts, np.int64)
    eps = np.where(counts > 0, math.inf, 0.0)
    if sigma <= 0:
        return eps
    # r / r0 per key, r0 by default from the key's largest size
    rr = np.full(len(counts), math.nan)
    for k in np.flatnonzero(counts):
        k0 = r0
        if k0 is None:
            k0 = int(table[k, :counts[k]].max()) / N_c * sigma
            k0 = min(max(k0, 1e-6), 1.0 / E)
        try:
            rr[k] = r_from_r0(k0, sigma) / k0
        except ValueError:
            pass                     # no finite moments bound: ∞
    live = np.flatnonzero(~np.isnan(rr))
    if not live.size:
        return eps
    # the keys by count, longest first: round i sums into the first m
    live = live[np.argsort(-counts[live], kind="stable")]
    n, rr = counts[live], rr[live]
    used = table[live, :n[0]]
    charged = np.arange(n[0]) < n[:, None]
    uniq = np.unique(used[charged])
    u_of = np.minimum(np.searchsorted(uniq, used), len(uniq) - 1)
    # moments_delta's per-size factors, as it computes them
    sc = [min(int(u), N_c - 1) for u in uniq]
    den1 = np.array([N_c * (N_c - s) * sigma ** 2 for s in sc])
    den2 = np.array([N_c * (N_c - s) ** 2 * sigma ** 3 for s in sc])
    s3 = np.array([float(s ** 3) for s in sc])
    cap = np.array([sigma ** 2 * math.log(max(N_c / (s * sigma), E))
                    for s in sc])
    lam = np.arange(1, lambda_max + 1)
    lamf, lam2, lam1 = (x.astype(np.float64)
                        for x in (lam, lam ** 2, lam + 1))
    # s²λ(λ+1) as a Python int, then one rounding to float64
    t1_num = np.multiply.outer(np.array([s * s for s in sc], object),
                               (lam * (lam + 1)).astype(object)
                               ).astype(np.float64)
    total = np.zeros((len(live), lambda_max))
    for i in range(int(n[0])):
        m = int(np.count_nonzero(n > i))
        u = u_of[:m, i]
        t1 = t1_num[u] / den1[u, None]
        t2 = rr[:m, None] * s3[u, None] * lam2 * lam1 / den2[u, None]
        total[:m] += t1 + t2
    # λ is valid up to the first size whose cap it passes
    capmin = np.where(charged, cap[u_of], math.inf).min(axis=1)
    valid = lam <= capmin[:, None]

    def delta_at(keys, e):
        best = np.where(valid[keys], total[keys] - lamf * e[:, None],
                        math.inf).min(axis=1)
        return np.array([math.exp(b) if b < math.inf else 1.0
                         for b in best.tolist()])

    lo = np.full(len(live), 1e-4)
    hi = np.full(len(live), 200.0)
    bounded = ~(delta_at(np.arange(len(live)), hi) > delta)
    act = np.flatnonzero(bounded)
    for _ in range(80):
        if not act.size:
            break
        mid = 0.5 * (lo[act] + hi[act])
        ok = delta_at(act, mid) <= delta
        hi[act] = np.where(ok, mid, hi[act])
        lo[act] = np.where(ok, lo[act], mid)
        act = act[~(hi[act] - lo[act] < tol)]
    eps[live] = np.where(bounded, hi, math.inf)
    return eps


def keys_accounting(table: np.ndarray, counts: np.ndarray,
                    inverse: np.ndarray, N_c: int, sigma: float,
                    delta: float, *, r0: Optional[float] = None
                    ) -> List[dict]:
    """Per-client (ε, σ, rounds-contributed) rows for a MetricsReport,
    from a fleet's ``participation_keys``: one ``keys_epsilon`` for
    every key, then one row per client.  An infinite ε (σ too small for
    δ at this N_c) is reported as ``None`` so the rows stay
    JSON-serializable."""
    eps = keys_epsilon(table, counts, N_c, sigma, delta, r0=r0)
    charged = np.arange(table.shape[1]) < np.asarray(counts)[:, None]
    samples = np.where(charged, table, 0).sum(axis=1)
    per_key = [(int(n), int(s), None if math.isinf(e) else float(e))
               for n, s, e in zip(counts, samples, eps.tolist())]
    sigma, delta = float(sigma), float(delta)
    return [{"client": c, "rounds_contributed": n, "samples": s,
             "sigma": sigma, "delta": delta, "epsilon": e}
            for c, (n, s, e) in enumerate(per_key[k]
                                          for k in inverse.tolist())]


def per_client_accounting(sizes_rows: Sequence[Sequence[int]], N_c: int,
                          sigma: float, delta: float, *,
                          r0: Optional[float] = None
                          ) -> List[dict]:
    """Per-client (ε, σ, rounds-contributed) rows for a MetricsReport.

    ``sizes_rows[c]`` is the sequence of sample sizes client c *actually
    sent* (its participation record, not the planned schedule) — in the
    paper's local-DP regime each client's privacy spend depends only on
    its own mechanism invocations, so the moments accountant runs per
    client over that row.  Identical rows are one key of
    ``keys_accounting``, so fleets with a common schedule cost one
    accountant pass per distinct row.
    """
    return keys_accounting(
        *participation_keys(sizes_rows, [len(r) for r in sizes_rows]),
        N_c, sigma, delta, r0=r0)
