"""Plain host reference of SPECTRA, kept with the benchmark.

A copy of the numpy path the program calls ``solver="spectra"``, written
out so that no later change to the program can move it:

* DECOMPOSE (Alg. 1) with greedy REFINE (Alg. 2): ``degree(D)`` rounds of
  a maximum-weight perfect matching (scipy's Jonker-Volgenant solver) under
  the node-coverage constraint, by the weight-augmentation bonus;
* SCHEDULE (Alg. 3): LPT over ``s`` switches, one δ per configuration;
* EQUALIZE (Alg. 4): split the longest configuration of the fullest switch
  onto the emptiest until the spread is at most δ;
* the §IV lower bound (Theorems 1 and 2, Property 2);
* the Eq. 3 check: a schedule covers its demand with permutations and
  nonnegative weights.

A schedule here is a plain tuple of per-switch lists of ``(perm, alpha)``.
Everything is float64 unless ``dtype`` asks for less: the benchmark's
control runs the same code with every stored number rounded to bfloat16.
"""

from __future__ import annotations

import heapq

import numpy as np
from scipy.optimize import linear_sum_assignment

def _round(x, dtype):
    """Round a float or array to ``dtype`` and return it as float64."""
    if dtype is None:
        return x
    return np.asarray(x, dtype=dtype).astype(np.float64)


def degree(S: np.ndarray) -> int:
    """Most nonzeros in any row or column of a 0/1 support."""
    if not S.any():
        return 0
    return int(max(S.sum(axis=1).max(), S.sum(axis=0).max()))


def _matching(D_rem: np.ndarray, S_rem: np.ndarray) -> np.ndarray:
    """Alg. 1 line 4: max-weight permutation covering every critical line
    of ``S_rem`` through one of its uncovered support entries."""
    row_deg, col_deg = S_rem.sum(axis=1), S_rem.sum(axis=0)
    k = max(row_deg.max(), col_deg.max())
    crit_r, crit_c = row_deg == k, col_deg == k
    base = np.maximum(D_rem, 0.0)
    M = float(base.sum()) + 1.0
    bonus = (crit_r[:, None].astype(np.float64) + crit_c[None, :]) * M
    rows, cols = linear_sum_assignment(base + np.where(S_rem, bonus, 0.0),
                                       maximize=True)
    perm = np.empty(D_rem.shape[0], dtype=np.int64)
    perm[rows] = cols
    return perm


def decompose(D: np.ndarray, dtype=None) -> tuple[list, list]:
    """Alg. 1 + greedy REFINE: ``degree(D)`` weighted permutations covering D."""
    n = D.shape[0]
    rows = np.arange(n)
    S_rem = D > 0
    D_rem = D.copy()
    perms, alphas = [], []
    while S_rem.any():
        perm = _matching(D_rem, S_rem)
        newly = S_rem[rows, perm]
        vals = D_rem[rows, perm][newly]
        alpha = float(_round(vals.min(), dtype)) if vals.size else 0.0
        perms.append(perm)
        alphas.append(alpha)
        D_rem[rows, perm] = _round(D_rem[rows, perm] - alpha, dtype)
        np.maximum(D_rem, 0.0, out=D_rem)
        S_rem[rows, perm] = False
    # Alg. 2: raise each weight by the largest residual left on its perm.
    R = D.copy()
    for perm, a in zip(perms, alphas):
        R[rows, perm] = _round(R[rows, perm] - a, dtype)
    np.maximum(R, 0.0, out=R)
    for i, perm in enumerate(perms):
        d = float(R[rows, perm].max())
        if d > 0.0:
            alphas[i] = float(_round(alphas[i] + d, dtype))
            R[rows, perm] = np.maximum(0.0, _round(R[rows, perm] - d, dtype))
    return perms, alphas


def schedule_lpt(perms, alphas, s: int, delta: float) -> tuple:
    """Alg. 3: heaviest first, each onto the least-loaded switch (lowest
    index on ties)."""
    order = np.argsort(-np.asarray(alphas), kind="stable")
    switches: tuple = tuple([] for _ in range(s))
    heap = [(0.0, h) for h in range(s)]
    heapq.heapify(heap)
    for i in order:
        load, h = heapq.heappop(heap)
        switches[h].append((perms[i], float(alphas[i])))
        heapq.heappush(heap, (load + delta + float(alphas[i]), h))
    return switches


def switch_loads(switches, delta: float) -> np.ndarray:
    return np.array([sum(a for _, a in sw) + delta * len(sw)
                     for sw in switches])


def makespan(switches, delta: float) -> float:
    return float(switch_loads(switches, delta).max())


def equalize(switches, delta: float, dtype=None) -> tuple:
    """Alg. 4 in place: move a τ slice of the fullest switch's longest
    configuration onto the emptiest switch until the spread is ≤ δ."""
    s = len(switches)
    if s <= 1:
        return switches
    loads = switch_loads(switches, delta)
    max_iters = 64 * (sum(len(sw) for sw in switches) + s) + 64
    for _ in range(max_iters):
        h_max, h_min = int(np.argmax(loads)), int(np.argmin(loads))
        if loads[h_max] - loads[h_min] <= delta:
            break
        src, dst = switches[h_max], switches[h_min]
        if not src:
            break
        z = int(np.argmax([a for _, a in src]))
        mu = (loads[h_max] + loads[h_min] + delta) / 2.0
        tau = float(_round(loads[h_max] - mu, dtype))
        perm, a = src[z]
        if tau <= 0 or a <= tau:
            break
        src[z] = (perm, float(_round(a - tau, dtype)))
        dst.append((perm.copy(), tau))
        loads[h_max] -= tau
        loads[h_min] += delta + tau
    return switches


def spectra(D: np.ndarray, s: int, delta: float, dtype=None) -> tuple:
    """DECOMPOSE → SCHEDULE → EQUALIZE; returns the per-switch schedule.

    With ``dtype`` (the control), the demand and every weight, residual
    and split are rounded to that type as they are stored."""
    D = _round(np.asarray(D, dtype=np.float64), dtype)
    perms, alphas = decompose(D, dtype)
    return equalize(schedule_lpt(perms, alphas, s, delta), delta, dtype)


# --------------------------------------------------------------- §IV bound
def _lb_theorem2(x: np.ndarray, s: int, delta: float) -> float:
    x = np.sort(x)[::-1]
    w = float(x.sum())
    pad = np.zeros(s * s + 2)
    pad[: len(x)] = x

    def xj(j: int) -> float:
        return float(pad[j - 1])

    opts = [xj(1), max(xj(2), (w + delta) / s, xj(s) + delta)]
    opts += [max(xj(m + 1), (w + m * delta) / s) for m in range(2, s * s + 1)]
    return delta + min(opts)


def lower_bound(D: np.ndarray, s: int, delta: float) -> float:
    """Property 2: the largest Theorem 1 / Theorem 2 bound over all lines."""
    D = np.asarray(D, dtype=np.float64)
    best = 0.0
    for M in (D, D.T):
        for line in M:
            nz = line[line > 0]
            if not nz.size:
                continue
            best = max(best, (float(nz.sum()) + delta * max(nz.size, s)) / s)
            if nz.size == s:
                best = max(best, _lb_theorem2(nz, s, delta))
    return best


# ------------------------------------------------------------- Eq. 3 check
def coverage(switches, n: int) -> np.ndarray:
    cov = np.zeros((n, n))
    rows = np.arange(n)
    for sw in switches:
        for perm, a in sw:
            cov[rows, perm] += a
    return cov


def malformed(switches, n: int, s: int) -> str | None:
    """Why a schedule is not a set of ``s`` switches of weighted
    permutations with nonnegative weights, or None."""
    if len(switches) != s:
        return f"{len(switches)} switches, not {s}"
    ident = np.arange(n)
    for h, sw in enumerate(switches):
        for j, (perm, a) in enumerate(sw):
            perm = np.asarray(perm)
            if perm.shape != (n,) or not np.array_equal(np.sort(perm), ident):
                return f"switch {h} configuration {j} is not a permutation"
            if not np.isfinite(a) or a < 0:
                return f"switch {h} configuration {j} has weight {a}"
    return None
