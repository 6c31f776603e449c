"""Pluggable device matchers: max-weight assignment solvers for DECOMPOSE.

This is the device counterpart of :mod:`repro.core.matching` — the inner
solver of SPECTRA's DECOMPOSE step — packaged as a small registry of
jittable, ``vmap``-able matchers sharing one bidding engine:

    auction      ε-scaling forward auction (Bertsekas, Jacobi variant):
                 every unassigned row bids at once, columns keep the best
                 bid. The default — fastest on the paper workloads.
    auction_fr   combined forward-reverse auction (Bertsekas-Castañón):
                 alternates row-side and column-side bidding rounds,
                 switching sides whenever the assignment grows. Dual-side
                 bidding breaks the one-sided price wars that sparse
                 large-n instances can trigger. Each round is one top-2
                 reduction: a column-side round is the row-side round on
                 ``W.T`` with the roles swapped, so each lane selects its
                 side's operands and bids once, batched or not.
    auction_fused  the whole hot loop owned by one fused implementation
                 (``kernels/auction_fused``): with ``use_kernel`` a single
                 Pallas kernel runs bid → price-update → assignment-flip
                 across ε-phase grid steps with prices in VMEM scratch and
                 lane-aligned 128-column tiles (no XLA round-trip between
                 rounds); without it, an exactly-matching jnp reference
                 whose O(n) segment-scatter rounds are the fast large-n
                 host path. The default matcher at n > 128.

``auction``/``auction_fr`` share the Pallas ``kernels/auction_bid`` top-2
reduction via ``use_kernel`` (a column-side round calls it on ``W.T``);
``auction_fused`` swaps the whole loop for ``kernels/auction_fused``.

The ε-schedule is n- and spread-aware. Two failure modes of a fixed
schedule, both observed at the paper's n=100 benchmark workload:

* **float32 price livelock** — with the node-coverage M-bonus folded into
  the weights, prices climb to ~``wmax``; once ε drops below the float32
  ulp at that magnitude, ``price + ε`` is a no-op and bidding loops
  forever (this alone produced the 1.36× quality gap: the matcher timed
  out, returned partial assignments, and DECOMPOSE inflated k from 16
  to 20). ``eps_floor`` pins the final ε at 2 ulps of ``wmax``.
* **phase-budget starvation** — 8 phases spanning ``wmax/2 → wmax·1e-6/n``
  shrink ε ~13× per phase at n=100, so late phases need thousands of
  bidding rounds. The phase count now grows with n so each phase refines
  ε by a bounded factor.

Matchers return ``(perm, converged)``. ``perm`` is always a valid
permutation: if the iteration budget is exhausted, leftover rows are paired
with leftover columns greedily (rank order) rather than returning ``-1``
sentinels — a ``-1`` silently corrupts downstream gathers — and
``converged=False`` reports the quality loss.

Optimality: with ε-scaling down to ``eps_final``, the assignment is within
``n·eps_final`` of the max weight (exact for integer weights when
``eps_final < 1/n``). The node-coverage constraint survives because the
M-bonus dominates ``n·eps_final``.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

_NEG = -1e30

# float32 prices saturate once ε < ulp(price); prices reach ~wmax, whose
# ulp is wmax·2^-23..2^-24 — floor ε at two ulps so increments always land.
_EPS_FLOOR = 2.0**-22


def default_num_phases(n: int) -> int:
    """n-aware ε-schedule length: bounded per-phase ε shrink factor.

    The ulp-floored final ε sits ~21 bits below ``wmax/2``; 8 phases keep
    the per-phase shrink ≤ ~8× at small n, 12 up to the paper's n=100
    benchmark, 16 in the pod-scale n ∈ {512, 1024} regime (at n > 256 the
    1e-6/n target is already below the float32 ulp floor, so extra phases
    buy smaller jumps, not smaller ε — measured necessary for the
    property-test optimality rate at n=512).
    """
    if n <= 32:
        return 8
    if n <= 256:
        return 12
    return 16


# Shape-bucket autotuning: the matcher ``repro.api`` picks per shape bucket
# when the caller didn't name one. ``auction`` wins below the first
# threshold (fastest on the paper workloads); between the thresholds the
# combined forward-reverse auction's dual-side bidding is the robust
# default against the one-sided price wars sparse large-n instances can
# trigger (measured at moe n=64 and benchmark n=100: identical 1.0000
# quality, converged); above the second, the fused auction owns the loop —
# re-measured on the BENCH_matching workload (sum-of-16-permutations + the
# DECOMPOSE M-bonus, CPU host, jnp paths): per-dispatch auction_fused vs
# auction 0.37s vs 0.72s at n=256 (1.9×), 2.8s vs 10.8s at n=512 (3.8×),
# 22.9s vs 66.9s at n=1024 (2.9×), all at quality ratio 1.0000 (fused is
# also fastest at n=100: 20ms vs 34ms, but auction_fr's dual-side bidding
# stays the mid-range default for robustness on sparse instances).
# Override per call via ``SolveOptions.extra["matcher"]`` or globally via
# ``set_default_matcher_policy``.
AUTOTUNE_N_THRESHOLD = 32
AUTOTUNE_FUSED_N_THRESHOLD = 128

_DEFAULT_POLICY = None  # None → built-in threshold rule


def default_matcher(n: int) -> str:
    """Registry default for an (n, n) instance (see AUTOTUNE_N_THRESHOLD /
    AUTOTUNE_FUSED_N_THRESHOLD)."""
    if _DEFAULT_POLICY is not None:
        name = _DEFAULT_POLICY(n)
        if name not in MATCHERS:
            # The install-time probe only sees one n; an n-dependent policy
            # can still return a bad name for other sizes — fail here with
            # the policy named, not deep inside a jitted dispatch.
            raise KeyError(
                f"default matcher policy returned unknown matcher {name!r} "
                f"for n={n}; available: {list_matchers()}"
            )
        return name
    if n <= AUTOTUNE_N_THRESHOLD:
        return "auction"
    if n <= AUTOTUNE_FUSED_N_THRESHOLD:
        return "auction_fr"
    return "auction_fused"


def set_default_matcher_policy(policy) -> None:
    """Install ``policy(n) -> matcher name`` as the autotuning rule
    (``None`` restores the built-in threshold rule)."""
    global _DEFAULT_POLICY
    if policy is not None:
        name = policy(8)
        if name not in MATCHERS:
            raise KeyError(
                f"policy returned unknown matcher {name!r}; "
                f"available: {list_matchers()}"
            )
    _DEFAULT_POLICY = policy


def default_max_iters(n: int) -> int:
    """Per-phase bidding-round budget; contested columns serialize, so the
    budget grows with n."""
    return max(2000, 60 * n)


def _top2(W, prices, use_kernel: bool):
    """Per-row top-2 of ``W - prices`` — the shared bid reduction."""
    if use_kernel:
        from ...kernels.auction_bid.ops import masked_row_top2

        return masked_row_top2(W, prices)
    from ...kernels.auction_bid.ref import masked_row_top2_ref

    return masked_row_top2_ref(W, prices)


def _forward_round(W, row2col, col2row, prices, profits, eps, use_kernel):
    """One Jacobi bidding round: all unassigned rows bid, columns take max.

    Also maintains row profits (``π_i = v2 - ε`` for winners) so the same
    round, as ``_bid_round``, serves either side of the forward-reverse
    matcher; the plain forward matcher threads a zero array through
    unchanged cost.
    """
    with jax.named_scope("auction.forward_round"):
        return _bid_round(
            W, row2col, col2row, prices, profits, eps, use_kernel
        )


def _bid_round(W, row2col, col2row, prices, profits, eps, use_kernel):
    """The round of ``_forward_round``, outside any scope."""
    n = W.shape[0]
    arange = jnp.arange(n)
    unassigned = row2col < 0
    v1, v2, j1 = _top2(W, prices, use_kernel)
    # Row i's bid for its favorite column j1[i].
    bid = jnp.where(unassigned, W[arange, j1] - v2 + eps, _NEG)
    # Columns take the best bid (scatter-max via a dense (n, n) mask).
    B = jnp.full((n, n), _NEG, W.dtype).at[arange, j1].set(bid)
    col_best = B.max(axis=0)
    col_winner = B.argmax(axis=0)
    has_bid = col_best > _NEG / 2
    # Kick out previous owners of re-auctioned columns.
    kicked = jnp.where(has_bid & (col2row >= 0), col2row, n)
    row2col = row2col.at[kicked].set(-1, mode="drop")
    # Install winners.
    winner = jnp.where(has_bid, col_winner, n)
    row2col = row2col.at[winner].set(jnp.where(has_bid, arange, -1), mode="drop")
    col2row = jnp.where(has_bid, col_winner, col2row)
    prices = jnp.where(has_bid, col_best, prices)
    safe_winner = jnp.clip(col_winner, 0, n - 1)
    profits = profits.at[winner].set(
        jnp.where(has_bid, v2[safe_winner] - eps, 0.0), mode="drop"
    )
    return row2col, col2row, prices, profits


def _complete_greedy(row2col, col2row):
    """Pair leftover rows with leftover columns in rank order so the result
    is always a permutation (a ``-1`` corrupts downstream gathers)."""
    n = row2col.shape[0]
    un_r = row2col < 0
    un_c = col2row < 0
    rank_r = jnp.cumsum(un_r) - 1          # 0-based rank among unassigned rows
    order_c = jnp.argsort(~un_c, stable=True)  # unassigned columns first
    fill = order_c[jnp.clip(rank_r, 0, n - 1)].astype(row2col.dtype)
    return jnp.where(un_r, fill, row2col)


def _eps_schedule(W, num_phases: int):
    """Geometric ε schedule from ``wmax/2`` down to the ulp-floored final ε."""
    n = W.shape[0]
    wmax = jnp.maximum(jnp.abs(W).max(), 1e-12)
    eps_final = jnp.maximum(wmax * 1e-6 / n, wmax * _EPS_FLOOR)
    ratio = (eps_final / (wmax / 2.0)) ** (1.0 / max(num_phases - 1, 1))
    return (wmax / 2.0) * ratio ** jnp.arange(num_phases)


def _returns(perm, converged, prices, iters, with_prices, with_iters):
    """``(perm, converged)``, then the prices and the rounds per phase
    when asked for, in that order."""
    return (
        (perm, converged)
        + ((prices,) if with_prices else ())
        + ((iters,) if with_iters else ())
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_phases", "max_iters", "use_kernel", "with_prices", "with_iters",
    ),
)
def match_auction(
    W: jax.Array,
    *,
    num_phases: int | None = None,
    max_iters: int | None = None,
    use_kernel: bool = False,
    prices0: jax.Array | None = None,
    with_prices: bool = False,
    with_iters: bool = False,
) -> tuple[jax.Array, ...]:
    """Forward ε-scaling auction. Returns ``(perm, converged)``.

    ``prices0`` warm-starts the column dual prices (e.g. the final prices of
    a previous, similar instance — the online controller's cross-period
    carry). ε-scaling already re-derives the assignment from prices each
    phase, so a warm start is equivalent to having run one extra earlier
    phase: optimality is unaffected, convergence on near-repeated instances
    is faster. ``with_prices=True`` appends the final prices to the return
    for callers that carry them forward; ``with_iters=True`` appends the
    bidding rounds of each ε phase, ``(num_phases,)`` int32, after them.
    """
    W = W.astype(jnp.float32)
    n = W.shape[0]
    if num_phases is None:
        num_phases = default_num_phases(n)
    if max_iters is None:
        max_iters = default_max_iters(n)
    init_prices = (
        jnp.zeros((n,), jnp.float32)
        if prices0 is None
        else jnp.asarray(prices0, jnp.float32)
    )

    def phase(state, eps):
        _, _, prices = state
        # Each phase restarts the assignment but keeps learned prices.
        row2col = jnp.full((n,), -1, jnp.int32)
        col2row = jnp.full((n,), -1, jnp.int32)
        zeros = jnp.zeros((n,), jnp.float32)

        def cond(c):
            row2col, _, _, it = c
            return (row2col < 0).any() & (it < max_iters)

        def body(c):
            row2col, col2row, prices, it = c
            row2col, col2row, prices, _ = _forward_round(
                W, row2col, col2row, prices, zeros, eps, use_kernel
            )
            return row2col, col2row, prices, it + 1

        row2col, col2row, prices, it = jax.lax.while_loop(
            cond, body, (row2col, col2row, prices, jnp.int32(0))
        )
        return (row2col, col2row, prices), it

    state = (
        jnp.full((n,), -1, jnp.int32),
        jnp.full((n,), -1, jnp.int32),
        init_prices,
    )
    state, iters = jax.lax.scan(phase, state, _eps_schedule(W, num_phases))
    row2col, col2row, prices = state
    converged = (row2col >= 0).all()
    perm = _complete_greedy(row2col, col2row)
    return _returns(perm, converged, prices, iters, with_prices, with_iters)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_phases", "max_iters", "use_kernel", "with_prices", "with_iters",
    ),
)
def match_auction_fr(
    W: jax.Array,
    *,
    num_phases: int | None = None,
    max_iters: int | None = None,
    use_kernel: bool = False,
    prices0: jax.Array | None = None,
    with_prices: bool = False,
    with_iters: bool = False,
) -> tuple[jax.Array, ...]:
    """Combined forward-reverse auction. Returns ``(perm, converged)``.

    Rows and columns take turns bidding; the side flips whenever a round
    grows the assignment (Bertsekas-Castañón switching rule — the matched
    count never shrinks, so alternation cannot cycle). A column-side round
    is the row-side round on ``W.T`` with rows ↔ columns and prices ↔
    profits swapped, so each round selects its side's operands with
    ``jnp.where`` and runs ``_bid_round`` once. Under ``vmap`` the side is
    chosen per lane and every lane still runs one round per iteration (a
    ``lax.cond`` on the side would become a ``select`` that computes both
    rounds and keeps one). ``prices0`` /
    ``with_prices`` / ``with_iters`` behave as on ``match_auction``
    (warm-started column prices in, final prices and rounds per phase out).
    """
    W = W.astype(jnp.float32)
    n = W.shape[0]
    if num_phases is None:
        num_phases = default_num_phases(n)
    if max_iters is None:
        max_iters = default_max_iters(n)
    init_prices = (
        jnp.zeros((n,), jnp.float32)
        if prices0 is None
        else jnp.asarray(prices0, jnp.float32)
    )

    WT = W.T

    def phase(state, eps):
        _, _, prices, profits = state
        row2col = jnp.full((n,), -1, jnp.int32)
        col2row = jnp.full((n,), -1, jnp.int32)

        def cond(c):
            row2col, _, _, _, _, it = c
            return (row2col < 0).any() & (it < max_iters)

        def body(c):
            row2col, col2row, prices, profits, fwd, it = c
            before = (row2col >= 0).sum()

            def side(a, b):
                return jnp.where(fwd, a, b)

            r2c, c2r, p, q = _bid_round(
                side(W, WT), side(row2col, col2row), side(col2row, row2col),
                side(prices, profits), side(profits, prices), eps, use_kernel,
            )
            row2col, col2row = side(r2c, c2r), side(c2r, r2c)
            prices, profits = side(p, q), side(q, p)
            grew = (row2col >= 0).sum() > before
            return row2col, col2row, prices, profits, fwd ^ grew, it + 1

        row2col, col2row, prices, profits, _, it = jax.lax.while_loop(
            cond,
            body,
            (row2col, col2row, prices, profits, jnp.bool_(True), jnp.int32(0)),
        )
        return (row2col, col2row, prices, profits), it

    state = (
        jnp.full((n,), -1, jnp.int32),
        jnp.full((n,), -1, jnp.int32),
        init_prices,
        jnp.zeros((n,), jnp.float32),
    )
    state, iters = jax.lax.scan(phase, state, _eps_schedule(W, num_phases))
    row2col, col2row, prices, _ = state
    converged = (row2col >= 0).all()
    perm = _complete_greedy(row2col, col2row)
    return _returns(perm, converged, prices, iters, with_prices, with_iters)


def _polish_2swap(W, perm, max_swaps: int):
    """Greedy best-pair 2-swap polish: upgrades the auction's guarantee
    from n·eps_final-optimal to *also 2-opt* (no single transposition can
    improve the assignment).

    eps_final is ulp-floored (``_EPS_FLOOR``), and at pod scale the floor's
    slack reaches ~n·wmax·2⁻²² ≈ 0.3 weight units (n=1024, M-bonus
    regime) — enough room, in principle, for transposition-type errors the
    polish repairs for free (one iteration ≈ one bidding round's top-2
    pass; ``gain(i,i') = W[i,σ(i')] + W[i',σ(i)] − W[i,σ(i)] −
    W[i',σ(i')]``, best strictly-positive swap applied per iteration).
    Measured on the BENCH_matching workloads the auction already lands
    2-opt (the polish is a no-op pass) — this is a cheap worst-case bound,
    not the source of the large-n quality numbers. Coverage is safe: the
    M-bonus dominates any demand gain, so a weight-increasing swap never
    drops a covered critical line.
    """
    n = W.shape[0]
    rows = jnp.arange(n)

    def cond(carry):
        _, it, improved = carry
        return improved & (it < max_swaps)

    def body(carry):
        perm, it, _ = carry
        cur = W[rows, perm]
        cross = W[:, perm]  # cross[i, i'] = W[i, perm[i']]
        gain = cross + cross.T - cur[:, None] - cur[None, :]
        flat = jnp.argmax(gain)
        i, ip = flat // n, flat % n
        do = gain[i, ip] > 0
        pi, pip = perm[i], perm[ip]
        new_perm = perm.at[i].set(jnp.where(do, pip, pi)).at[ip].set(
            jnp.where(do, pi, pip)
        )
        return new_perm, it + 1, do

    perm, _, _ = jax.lax.while_loop(
        cond, body, (perm, jnp.int32(0), jnp.bool_(True))
    )
    return perm


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_phases", "max_iters", "use_kernel", "with_prices", "interpret",
        "with_iters",
    ),
)
def match_auction_fused(
    W: jax.Array,
    *,
    num_phases: int | None = None,
    max_iters: int | None = None,
    use_kernel: bool = False,
    prices0: jax.Array | None = None,
    with_prices: bool = False,
    interpret: bool | None = None,
    with_iters: bool = False,
) -> tuple[jax.Array, ...]:
    """Fully fused forward ε-scaling auction. Returns ``(perm, converged)``.

    The whole hot loop lives in ``kernels/auction_fused``: with
    ``use_kernel=True`` a single Pallas kernel runs every bidding round of
    every ε phase on-chip (prices in VMEM scratch across the phase grid,
    lane-aligned 128-column tiles at n ≥ 256 — the pod-scale path); with
    ``use_kernel=False`` the exactly-matching jnp reference, whose
    segment-scatter rounds are themselves several times cheaper than
    ``match_auction``'s whole-matrix rounds at large n. Shares this
    module's ε-schedule (ulp floor included), ``(perm, converged)``
    contract, greedy completion, and ``prices0``/``with_prices`` warm-start
    surface, then runs the ``_polish_2swap`` sweep so the result is also
    2-opt — a cheap worst-case guard against ε-floor transposition errors
    (measured a no-op on the benchmark workloads; see its docstring).
    ``interpret`` forces/disables Pallas interpret mode (``None`` → auto:
    interpret off-TPU). ``with_iters=True`` appends the bidding rounds of
    each ε phase run (after prices, when both are requested), from the
    kernel or the reference alike — the observable that shows cross-period
    warm starts converging in fewer rounds.

    **Warm ε-entry:** supplying ``prices0`` is declared "equivalent to
    having run the earlier phases already" (see ``match_auction``) — here
    that equivalence is cashed in. A warm dispatch enters the ε grid at its
    *tail* (the last ``max(2, num_phases // 2)`` phases), so cross-period
    price carry pays for roughly half the bidding phases instead of
    re-running the full schedule against already-converged prices. The
    optimality guarantee is unchanged — it comes from the final phase
    completing at the same ulp-floored ``eps_final`` (``converged`` still
    reports budget exhaustion); only the ramp that warm prices make
    redundant is skipped. Cold dispatches (no ``prices0``) are untouched.
    """
    from ...kernels.auction_fused.ops import fused_auction

    W = W.astype(jnp.float32)
    n = W.shape[0]
    if num_phases is None:
        num_phases = default_num_phases(n)
    if max_iters is None:
        max_iters = default_max_iters(n)
    eps_schedule = _eps_schedule(W, num_phases)
    if prices0 is None:
        init_prices = jnp.zeros((n,), jnp.float32)
    else:
        init_prices = jnp.asarray(prices0, jnp.float32)
        eps_schedule = eps_schedule[-max(2, num_phases // 2):]
    out = fused_auction(
        W,
        init_prices,
        eps_schedule,
        max_iters=max_iters,
        use_kernel=use_kernel,
        interpret=interpret,
        with_iters=with_iters,
    )
    row2col, col2row, prices = out[:3]
    converged = (row2col >= 0).all()
    perm = _complete_greedy(row2col, col2row)
    perm = _polish_2swap(W, perm, max_swaps=2 * n)
    iters = out[3] if with_iters else None
    return _returns(perm, converged, prices, iters, with_prices, with_iters)


# --------------------------------------------------------------- registry

MatcherFn = Callable[..., tuple[jax.Array, jax.Array]]

MATCHERS: dict[str, MatcherFn] = {
    "auction": match_auction,
    "auction_fr": match_auction_fr,
    "auction_fused": match_auction_fused,
}


def get_matcher(name: str) -> MatcherFn:
    if name not in MATCHERS:
        raise KeyError(f"unknown matcher {name!r}; available: {list_matchers()}")
    return MATCHERS[name]


def list_matchers() -> list[str]:
    return sorted(MATCHERS)


def register_matcher(name: str, fn: MatcherFn, *, overwrite: bool = False) -> None:
    """Add a device matcher: ``fn(W, *, num_phases, max_iters, use_kernel,
    with_iters) -> (perm, converged[, rounds per ε phase])``, jittable and
    vmappable; DECOMPOSE always asks for the rounds (``with_iters=True``).
    Matchers that support warm starts additionally accept ``prices0``
    (initial dual prices) and ``with_prices=True`` (append final prices,
    before the rounds) — the online controller only requests those from
    matchers that advertise them."""
    if name in MATCHERS and not overwrite:
        raise ValueError(f"matcher {name!r} already registered")
    replacing = name in MATCHERS
    MATCHERS[name] = fn
    if replacing:
        # Jitted consumers resolve the name at trace time and key their
        # caches on the string — drop them so the replacement takes effect.
        from .decompose_jax import decompose_jax, decompose_jax_prices
        from .e2e import spectra_jax_e2e, spectra_jax_e2e_many
        from .online_jax import online_step_jax, spectra_online_scan

        for jitted in (
            decompose_jax, decompose_jax_prices,
            spectra_jax_e2e, spectra_jax_e2e_many,
            online_step_jax, spectra_online_scan,
        ):
            jitted.clear_cache()
