"""On-device (TPU) SPECTRA DECOMPOSE + LPT SCHEDULE, fully in JAX.

Mirrors ``repro.core.decompose``/``schedule`` with dense array state inside
``lax.while_loop``/``scan`` so the controller's scheduling computation can run
on the accelerator itself and be ``vmap``-ed over batches of demand matrices
(DESIGN.md §4). The constrained MWM goes through a pluggable device matcher
(:mod:`repro.core.jaxopt.matching` — ε-scaling auction by default, selectable
via ``matcher=``); the node-coverage constraint is encoded in the weights
(M-bonus), exactly as in the numpy path.

Beyond Alg. 1+2, ``repair_rounds > 0`` enables a bounded device local-search
pass after the greedy REFINE: repeated shrink sweeps re-extract α mass that
REFINE over-provisioned (each sweep lowers every α by the minimum coverage
slack along its permutation), so one weak matching round no longer
permanently inflates the decomposition's total weight. Rounds whose α
shrinks to zero are compacted to the tail and dropped from ``k`` — they
would otherwise still cost δ in the schedule.

EQUALIZE runs on device too: the decomposition and LPT assignment produced
here feed the dense ``repro.core.schedule_ir.DeviceSchedule`` slot table, on
which ``equalize_jax`` (Alg. 4 as a ``lax.while_loop``) operates — see
``repro.core.jaxopt.e2e.spectra_jax_e2e`` for the fused single-call pipeline.
``to_decomposition`` + ``repro.core.schedule_lpt`` + ``repro.core.equalize``
remain available to materialize/rebuild a host schedule from the raw
decomposition.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .matching import get_matcher
from ..decompose import Decomposition


class JaxDecomposition(NamedTuple):
    perms: jax.Array   # (n, n) int32; row r = permutation of round r (padded)
    alphas: jax.Array  # (n,) float32; 0 for padded rounds
    k: jax.Array       # () int32: number of real rounds
    converged: jax.Array  # () bool: all matcher calls converged
    bid_rounds: jax.Array  # () int32: this instance's bidding rounds, summed
                           # over its DECOMPOSE matchings
    bid_rounds_run: jax.Array  # () int32: the rounds the (batched) loops
                               # ran while this instance was matching


def _decompose(
    D: jax.Array,
    *,
    use_kernel: bool,
    matcher: str,
    repair_rounds: int,
    carry_prices: bool,
    prices0: jax.Array | None,
    axis_name: str | None = None,
) -> tuple[JaxDecomposition, jax.Array]:
    """Shared impl; returns ``(dec, final dual prices)``.

    ``carry_prices=True`` threads the matcher's column dual prices through
    the rounds (each round warm-starts from the previous round's finals)
    and seeds round 0 with ``prices0`` — the online controller's
    cross-period warm start. ``False`` reproduces the stateless behavior
    bit-for-bit (every round starts from zero prices).

    ``axis_name`` names the ``vmap`` axis this call runs under, if any. A
    batched ``while`` runs until its slowest instance stops, so each ε
    phase of a lockstep matcher costs the batch the most rounds any
    instance bid in it: ``bid_rounds_run`` adds that maximum (a ``pmax``
    over the axis) where ``bid_rounds`` adds the instance's own. The fused
    kernel runs each instance's loop in its own grid step, so there the
    two are equal.
    """
    match = get_matcher(matcher)
    lockstep = axis_name is not None and not (
        use_kernel and matcher == "auction_fused"
    )
    D = D.astype(jnp.float32)
    n = D.shape[0]
    arange = jnp.arange(n)
    init_prices = (
        jnp.zeros((n,), jnp.float32)
        if prices0 is None
        else jnp.asarray(prices0, jnp.float32)
    )

    def cond(st):
        _, S_rem, _, _, i, _, _, _, _ = st
        return S_rem.any() & (i < n)

    def body(st):
        D_rem, S_rem, perms, alphas, i, conv, prices, rounds, run = st
        with jax.named_scope("spectra.decompose.peel"):
            row_deg = S_rem.sum(axis=1)
            col_deg = S_rem.sum(axis=0)
            k = jnp.maximum(row_deg.max(), col_deg.max())
            crit_r = (row_deg == k) & (k > 0)
            crit_c = (col_deg == k) & (k > 0)
            base = jnp.maximum(D_rem, 0.0)
            # Dominance constant: any permutation serves at most the sum of
            # row maxima, so this M already forces the max bonus count.
            # (Tighter than sum(D)+1: auction prices scale with M, and
            # float32 price resolution — hence matcher convergence —
            # improves as M shrinks.) The bonus-level separation must
            # dominate the matcher's n·ε optimality slack, which scales
            # with the weight magnitude (ε is ulp-floored at wmax·2⁻²²,
            # wmax ≤ 3M) — hence the relative margin on top of the
            # absolute +1.
            M = (base.max(axis=1).sum() + 1.0) * (1.0 + n * 2.0**-19)
            bonus = M * (
                crit_r[:, None].astype(jnp.float32) + crit_c[None, :]
            )
            W = base + jnp.where(S_rem, bonus, 0.0)
        with jax.named_scope("spectra.decompose.match"):
            if carry_prices:
                perm, ok, prices, iters = match(
                    W, use_kernel=use_kernel, prices0=prices,
                    with_prices=True, with_iters=True,
                )
            else:
                perm, ok, iters = match(
                    W, use_kernel=use_kernel, with_iters=True
                )
            rounds = rounds + iters.sum()
            if lockstep:
                iters = jax.lax.pmax(iters, axis_name)
            run = run + iters.sum()
        with jax.named_scope("spectra.decompose.peel"):
            newly = S_rem[arange, perm]
            # α = min D_rem over *newly covered* support, exactly the numpy
            # "covered_support" rule: a round that newly covers nothing gets
            # α=0 (guarding on newly.any() keeps the inf mask from ever
            # escaping).
            vals = jnp.where(newly, D_rem[arange, perm], jnp.inf)
            alpha = jnp.where(newly.any(), vals.min(), 0.0)
            D_rem = jnp.maximum(D_rem.at[arange, perm].add(-alpha), 0.0)
            S_rem = S_rem.at[arange, perm].set(False)
            perms = perms.at[i].set(perm.astype(jnp.int32))
            alphas = alphas.at[i].set(alpha)
        return (
            D_rem, S_rem, perms, alphas, i + 1, conv & ok, prices, rounds, run
        )

    init = (
        D,
        D > 0,
        jnp.broadcast_to(arange[None, :], (n, n)).astype(jnp.int32),
        jnp.zeros((n,), jnp.float32),
        jnp.int32(0),
        jnp.bool_(True),
        init_prices,
        jnp.int32(0),
        jnp.int32(0),
    )
    _, _, perms, alphas, k, conv, prices, rounds, run = jax.lax.while_loop(
        cond, body, init
    )

    cov_idx = (jnp.broadcast_to(arange[None, :], (n, n)), perms)
    round_live = (jnp.arange(n) < k)[:, None]

    def coverage(al):
        return jnp.zeros_like(D).at[cov_idx].add(al[:, None] * round_live)

    def refine_body(r, carry):
        R, alphas = carry
        perm = perms[r]
        d = jnp.maximum(R[arange, perm].max(), 0.0)
        d = jnp.where(r < k, d, 0.0)
        alphas = alphas.at[r].add(d)
        R = R.at[arange, perm].add(-d)
        R = jnp.maximum(R, 0.0)
        return R, alphas

    # Greedy REFINE (Alg. 2) over all rounds (padded rounds see zero residual).
    with jax.named_scope("spectra.refine"):
        R0 = jnp.maximum(D - coverage(alphas), 0.0)
        _, alphas = jax.lax.fori_loop(0, n, refine_body, (R0, alphas))

    if repair_rounds:
        with jax.named_scope("spectra.repair"):
            perms, alphas, k = _repair(
                D, perms, alphas, k, coverage, repair_rounds
            )
    dec = JaxDecomposition(
        perms=perms, alphas=alphas, k=k, converged=conv,
        bid_rounds=rounds, bid_rounds_run=run,
    )
    return dec, prices


@functools.partial(
    jax.jit,
    static_argnames=("use_kernel", "matcher", "repair_rounds", "axis_name"),
)
def decompose_jax(
    D: jax.Array,
    *,
    use_kernel: bool = False,
    matcher: str = "auction",
    repair_rounds: int = 0,
    axis_name: str | None = None,
) -> JaxDecomposition:
    """Exactly-k decomposition of D (Alg. 1 + greedy REFINE), on device.

    ``matcher`` picks the device MWM solver from ``matching.MATCHERS``;
    ``repair_rounds`` bounds the post-REFINE local-search sweeps (0 keeps
    the paper-faithful Alg. 1+2 output bit-for-bit). ``axis_name`` names
    the ``vmap`` axis of a batched call (see ``_decompose``).
    """
    dec, _ = _decompose(
        D, use_kernel=use_kernel, matcher=matcher,
        repair_rounds=repair_rounds, carry_prices=False, prices0=None,
        axis_name=axis_name,
    )
    return dec


@functools.partial(
    jax.jit, static_argnames=("use_kernel", "matcher", "repair_rounds")
)
def decompose_jax_prices(
    D: jax.Array,
    prices0: jax.Array,
    *,
    use_kernel: bool = False,
    matcher: str = "auction",
    repair_rounds: int = 0,
) -> tuple[JaxDecomposition, jax.Array]:
    """Warm-started decomposition: seed the matcher's dual prices with
    ``prices0`` (e.g. the previous controller period's finals) and return
    ``(dec, final prices)`` so the caller can carry them forward. Requires
    a matcher that supports ``prices0``/``with_prices`` (both built-ins do).
    """
    return _decompose(
        D, use_kernel=use_kernel, matcher=matcher,
        repair_rounds=repair_rounds, carry_prices=True, prices0=prices0,
    )


def _repair(D, perms, alphas, k, coverage, repair_rounds: int):
    """Bounded local search on the refined weights (2-opt α re-extraction).

    REFINE only ever raises weights, so entries covered by several rounds
    end up over-provisioned. Each sweep walks the rounds and shrinks α_r by
    the minimum slack ``(Σ α P − D)`` along its permutation — the largest
    reduction that keeps coverage — wrapping the freed mass back into the
    makespan. Sweeps repeat (bounded by ``repair_rounds``) until a full
    pass changes nothing; rounds whose α hits zero are compacted to the
    tail and dropped from ``k`` so they stop costing δ.
    """
    n = D.shape[0]
    arange = jnp.arange(n)

    def sweep(carry):
        alphas, rounds_left, improved = carry
        slack = coverage(alphas) - D

        def one(r, c):
            slack, al = c
            perm = perms[r]
            d = jnp.minimum(slack[arange, perm].min(), al[r])
            d = jnp.where(r < k, jnp.maximum(d, 0.0), 0.0)
            al = al.at[r].add(-d)
            slack = slack.at[arange, perm].add(-d)
            return slack, al

        _, new = jax.lax.fori_loop(0, n, one, (slack, alphas))
        return new, rounds_left - 1, (new < alphas).any()

    alphas, _, _ = jax.lax.while_loop(
        lambda c: c[2] & (c[1] > 0),
        sweep,
        (alphas, jnp.int32(repair_rounds), jnp.bool_(True)),
    )
    # Compact: live rounds (α > 0) first in original order; dead rounds
    # join the padding so LPT/EQUALIZE slot accounting stays contiguous.
    live = (alphas > 0) & (jnp.arange(n) < k)
    order = jnp.argsort(~live, stable=True)
    return perms[order], jnp.where(live, alphas, 0.0)[order], live.sum()


@functools.partial(jax.jit, static_argnames=("s",))
def lpt_schedule_jax(dec: JaxDecomposition, s: int, delta: jax.Array):
    """Alg. 3 on device: returns (assignment (n,), loads (s,), makespan)."""
    n = dec.alphas.shape[0]
    valid = (jnp.arange(n) < dec.k) & (dec.alphas > 0)
    order = jnp.argsort(jnp.where(valid, -dec.alphas, jnp.inf))

    def place(loads, idx):
        a = dec.alphas[idx]
        is_real = jnp.take(valid, idx)
        h = jnp.argmin(loads)
        loads = jnp.where(is_real, loads.at[h].add(delta + a), loads)
        return loads, jnp.where(is_real, h, -1)

    loads, assignment_sorted = jax.lax.scan(place, jnp.zeros((s,), jnp.float32), order)
    assignment = jnp.full((n,), -1, jnp.int32).at[order].set(
        assignment_sorted.astype(jnp.int32)
    )
    return assignment, loads, loads.max()


def spectra_jax(
    D: jax.Array,
    s: int,
    delta: float,
    *,
    use_kernel: bool = False,
    matcher: str = "auction",
    repair_rounds: int = 0,
):
    """DECOMPOSE + LPT on device; returns (dec, assignment, loads, makespan)."""
    dec = decompose_jax(
        D, use_kernel=use_kernel, matcher=matcher, repair_rounds=repair_rounds
    )
    assignment, loads, makespan = lpt_schedule_jax(dec, s, jnp.float32(delta))
    return dec, assignment, loads, makespan


def to_decomposition(dec: JaxDecomposition) -> Decomposition:
    """Materialize on host as a numpy Decomposition (for EQUALIZE etc.).

    Zero-α rounds (possible after repair) are dropped — they carry no
    weight and would only add δ-cost configs to a host schedule.
    """
    import numpy as np

    k = int(dec.k)
    perms = np.asarray(dec.perms)[:k]
    alphas = np.asarray(dec.alphas)[:k]
    keep = alphas > 0
    return Decomposition(
        perms=[p.astype(np.int64) for p, kp in zip(perms, keep) if kp],
        alphas=[float(a) for a, kp in zip(alphas, keep) if kp],
    )
