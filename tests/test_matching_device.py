"""Device matcher registry: optimality vs scipy, both kernel paths, repair.

The optimality property the subsystem rests on: with the n-aware ε-schedule
scaled down to ``eps_final``, every registered matcher's assignment is
within ``n·eps_final`` of ``scipy.optimize.linear_sum_assignment`` — exact
for integer weights (``n·eps_final < 1`` at these sizes, since the
ulp-floored ``eps_final ≈ wmax·2⁻²²``). Runs both the jnp reference and the
Pallas ``use_kernel`` top-2 paths.
"""

import numpy as np
import pytest
from scipy.optimize import linear_sum_assignment

from repro.core.decompose import Decomposition, decompose, degree
from repro.core.jaxopt.decompose_jax import decompose_jax, to_decomposition
from repro.core.jaxopt.matching import (
    MATCHERS,
    get_matcher,
    list_matchers,
    match_auction,
    register_matcher,
)

jnp = pytest.importorskip("jax.numpy")

ALL_MATCHERS = sorted(MATCHERS)


def _optimal(W):
    ri, ci = linear_sum_assignment(W, maximize=True)
    return W[ri, ci].sum()


def _matched_weight(W, perm):
    perm = np.asarray(perm)
    n = W.shape[0]
    assert len(np.unique(perm)) == n, "matcher returned a non-permutation"
    return W[np.arange(n), perm].sum()


# ------------------------------------------------------------- optimality

@pytest.mark.parametrize("matcher", ALL_MATCHERS)
@pytest.mark.parametrize("n", [8, 16, 33, 64])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_matcher_exact_on_random_integers(matcher, n, seed):
    rng = np.random.default_rng(seed)
    W = rng.integers(0, 1000, (n, n)).astype(np.float32)
    perm, conv = get_matcher(matcher)(jnp.asarray(W))
    assert bool(conv)
    # n·eps_final < 1 here, so integer weights are matched exactly.
    assert _matched_weight(W, perm) == _optimal(W)


@pytest.mark.parametrize("matcher", ALL_MATCHERS)
@pytest.mark.parametrize("n", [16, 33, 64])
@pytest.mark.parametrize("density", [0.1, 0.3])
def test_matcher_near_optimal_on_sparse_floats(matcher, n, density):
    rng = np.random.default_rng(n * 10 + int(density * 10))
    W = (rng.random((n, n)) * (rng.random((n, n)) < density)).astype(np.float32)
    perm, conv = get_matcher(matcher)(jnp.asarray(W))
    assert bool(conv)
    opt = _optimal(W)
    assert _matched_weight(W, perm) >= opt - max(1e-3 * opt, 1e-6)


@pytest.mark.parametrize("matcher", ALL_MATCHERS)
@pytest.mark.parametrize("n", [16, 64])
def test_matcher_kernel_path_matches_reference(matcher, n):
    rng = np.random.default_rng(n)
    W = rng.integers(0, 500, (n, n)).astype(np.float32)
    fn = get_matcher(matcher)
    p_ref, conv_ref = fn(jnp.asarray(W), use_kernel=False)
    p_kern, conv_kern = fn(jnp.asarray(W), use_kernel=True)
    assert bool(conv_ref) and bool(conv_kern)
    # Both paths must reach the same (optimal) weight; tie-breaks may differ.
    opt = _optimal(W)
    assert _matched_weight(W, p_ref) == opt
    assert _matched_weight(W, p_kern) == opt


def test_matcher_large_sparse_with_coverage_bonus():
    # The regime that broke the fixed 8-phase schedule: n=100, sparse
    # support, node-coverage M-bonus folded into the weights (prices climb
    # to ~wmax, where a too-small ε is below the float32 ulp and livelocks).
    from repro.traffic.workloads import benchmark_workload

    D = benchmark_workload(rng=np.random.default_rng(0))
    S = D > 0
    row_deg, col_deg = S.sum(1), S.sum(0)
    k = max(row_deg.max(), col_deg.max())
    M = np.maximum(D, 0.0).max(axis=1).sum() + 1.0
    bonus = M * ((row_deg == k)[:, None].astype(float) + (col_deg == k)[None, :])
    W = (np.maximum(D, 0.0) + np.where(S, bonus, 0.0)).astype(np.float32)
    opt = _optimal(W)
    for matcher in ALL_MATCHERS:
        perm, conv = get_matcher(matcher)(jnp.asarray(W))
        assert bool(conv), matcher
        got = _matched_weight(W, perm)
        assert got >= opt - 1e-4 * opt, matcher


# --------------------------------------------------------------- registry

def test_registry_round_trip_and_errors():
    assert {"auction", "auction_fr"} <= set(list_matchers())
    with pytest.raises(KeyError, match="unknown matcher"):
        get_matcher("hungarian")
    with pytest.raises(ValueError, match="already registered"):
        register_matcher("auction", match_auction)
    register_matcher("auction2", match_auction)
    try:
        assert get_matcher("auction2") is match_auction
    finally:
        del MATCHERS["auction2"]


def test_unconverged_matcher_still_returns_a_permutation():
    # Starve the iteration budget: converged=False must come with a valid
    # (greedily completed) permutation, never -1 sentinels that would
    # corrupt downstream gathers.
    rng = np.random.default_rng(0)
    W = rng.random((24, 24)).astype(np.float32)
    perm, conv = match_auction(jnp.asarray(W), num_phases=2, max_iters=1)
    assert not bool(conv)
    perm = np.asarray(perm)
    assert len(np.unique(perm)) == 24
    assert (perm >= 0).all()


# -------------------------------------------------- decompose integration

@pytest.mark.parametrize("matcher", ALL_MATCHERS)
def test_decompose_jax_matcher_choice(matcher):
    rng = np.random.default_rng(4)
    n = 16
    D = (rng.random((n, n)) * (rng.random((n, n)) < 0.3)).astype(np.float32)
    D[0, 1] = 0.9
    dec = decompose_jax(jnp.asarray(D), matcher=matcher)
    assert bool(dec.converged)
    assert int(dec.k) == degree(D)
    assert to_decomposition(dec).covers(D, tol=1e-5)


def test_decompose_jax_unknown_matcher():
    with pytest.raises(KeyError, match="unknown matcher"):
        decompose_jax(jnp.zeros((4, 4)), matcher="nope")


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_repair_shrinks_weight_and_keeps_coverage(seed):
    rng = np.random.default_rng(seed)
    n = 20
    D = (rng.random((n, n)) * (rng.random((n, n)) < 0.4)).astype(np.float32)
    D[1, 2] = 1.0
    plain = decompose_jax(jnp.asarray(D))
    repaired = decompose_jax(jnp.asarray(D), repair_rounds=2)
    dp, dr = to_decomposition(plain), to_decomposition(repaired)
    assert dp.covers(D, tol=1e-4) and dr.covers(D, tol=1e-4)
    # The local search only ever removes over-provisioned mass, and dropped
    # zero-α rounds can only shrink k.
    assert dr.total_weight() <= dp.total_weight() + 1e-5
    assert dr.k <= dp.k
    # Repaired alphas are compacted: every surviving round carries weight.
    assert all(a > 0 for a in dr.alphas)
    # Host reference: repair can only help the covered total, never break it.
    host = decompose(np.asarray(D, np.float64))
    assert dr.total_weight() <= host.total_weight() * 1.05 + 1e-6


def test_repair_noop_on_tight_decompositions():
    # Demand that IS a weighted permutation decomposes tightly (k=1, zero
    # slack): repair must change nothing (guard for the repair sweep's
    # slack accounting — it may only remove genuinely over-provisioned mass).
    rng = np.random.default_rng(7)
    n = 12
    D = np.zeros((n, n))
    D[np.arange(n), rng.permutation(n)] = 0.7
    plain = decompose_jax(jnp.asarray(D, jnp.float32))
    repaired = decompose_jax(jnp.asarray(D, jnp.float32), repair_rounds=3)
    assert int(plain.k) == int(repaired.k) == 1
    np.testing.assert_allclose(
        np.asarray(plain.alphas), np.asarray(repaired.alphas), atol=1e-6
    )


# ------------------------------------- auction_fr: one round per iteration

def _fr_two_branch(W, prices0, use_kernel):
    """The forward-reverse auction as a per-iteration ``lax.cond`` between a
    row-side round on ``W`` and a column-side round on ``W.T``: the
    reference for the single-round loop. Returns ``(perm, prices, rounds
    per phase)``."""
    import jax

    from repro.core.jaxopt.matching import (
        _bid_round,
        _complete_greedy,
        _eps_schedule,
        default_max_iters,
        default_num_phases,
    )

    W = W.astype(jnp.float32)
    n = W.shape[0]
    max_iters = default_max_iters(n)

    def forward(a, eps):
        return _bid_round(W, *a, eps, use_kernel)

    def reverse(a, eps):
        row2col, col2row, prices, profits = a
        col2row, row2col, profits, prices = _bid_round(
            W.T, col2row, row2col, profits, prices, eps, use_kernel
        )
        return row2col, col2row, prices, profits

    def phase(state, eps):
        _, _, prices, profits = state
        row2col = jnp.full((n,), -1, jnp.int32)
        col2row = jnp.full((n,), -1, jnp.int32)

        def cond(c):
            return (c[0] < 0).any() & (c[5] < max_iters)

        def body(c):
            row2col, col2row, prices, profits, fwd, it = c
            before = (row2col >= 0).sum()
            row2col, col2row, prices, profits = jax.lax.cond(
                fwd,
                lambda a: forward(a, eps),
                lambda a: reverse(a, eps),
                (row2col, col2row, prices, profits),
            )
            grew = (row2col >= 0).sum() > before
            return row2col, col2row, prices, profits, fwd ^ grew, it + 1

        c = jax.lax.while_loop(
            cond, body,
            (row2col, col2row, prices, profits, jnp.bool_(True), jnp.int32(0)),
        )
        return c[:4], c[5]

    state = (
        jnp.full((n,), -1, jnp.int32),
        jnp.full((n,), -1, jnp.int32),
        jnp.asarray(prices0, jnp.float32),
        jnp.zeros((n,), jnp.float32),
    )
    (row2col, col2row, prices, _), iters = jax.jit(
        lambda s: jax.lax.scan(
            phase, s, _eps_schedule(W, default_num_phases(n))
        )
    )(state)
    return _complete_greedy(row2col, col2row), prices, iters


@pytest.mark.parametrize(
    "n, use_kernel, warm",
    [(5, False, False), (37, False, False), (5, True, False), (37, False, True)],
)
def test_auction_fr_vmapped_lanes_match_two_branch_reference(n, use_kernel, warm):
    """Lanes on different sides in the same iteration each run exactly the
    round the per-lane ``cond`` reference runs: bit-identical perms, prices
    and rounds per phase."""
    import jax

    from repro.core.jaxopt.matching import match_auction_fr

    rng = np.random.default_rng(1000 + n)
    Ws = jnp.asarray(
        np.stack([
            rng.random((n, n)),
            rng.integers(0, 50, (n, n)),
            rng.random((n, n)) * (rng.random((n, n)) < 0.3),
        ]),
        jnp.float32,
    )
    p0 = jnp.asarray(
        rng.random((3, n)) if warm else np.zeros((3, n)), jnp.float32
    )
    perm, conv, prices, iters = jax.vmap(
        lambda W, p: match_auction_fr(
            W, prices0=p, use_kernel=use_kernel, with_prices=True,
            with_iters=True,
        )
    )(Ws, p0)
    assert bool(conv.all())
    for lane in range(3):
        ref = _fr_two_branch(Ws[lane], p0[lane], use_kernel)
        for name, got, want in zip(
            ("perm", "prices", "rounds"), (perm, prices, iters), ref
        ):
            np.testing.assert_array_equal(
                np.asarray(got[lane]), np.asarray(want),
                err_msg=f"lane {lane}: {name}",
            )
    # The lanes diverge, so the batch runs them on different sides.
    assert len({tuple(np.asarray(r)) for r in iters}) > 1


def _count_primitive(jaxpr, name: str) -> int:
    """Occurrences of primitive ``name`` in ``jaxpr`` and every sub-jaxpr."""
    count = 0
    for eqn in jaxpr.eqns:
        count += eqn.primitive.name == name
        for sub in _sub_jaxprs(eqn.params.values()):
            count += _count_primitive(sub, name)
    return count


def _sub_jaxprs(values):
    for v in values:
        if isinstance(v, (list, tuple)):
            yield from _sub_jaxprs(v)
        elif hasattr(v, "eqns"):
            yield v
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            yield v.jaxpr


def _while_bodies(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            yield eqn.params["body_jaxpr"].jaxpr
        for sub in _sub_jaxprs(eqn.params.values()):
            yield from _while_bodies(sub)


@pytest.mark.parametrize("batched", [True, False])
def test_auction_fr_bids_once_per_iteration(batched):
    """The bidding ``while`` body calls the top-2 kernel once, batched or
    not: no second round for the other side."""
    import functools

    import jax

    from repro.core.jaxopt.matching import match_auction_fr

    fn = functools.partial(match_auction_fr, use_kernel=True)
    W = jnp.zeros((3, 8, 8) if batched else (8, 8), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.vmap(fn) if batched else fn)(W).jaxpr
    bodies = [
        b for b in _while_bodies(jaxpr) if _count_primitive(b, "pallas_call")
    ]
    assert len(bodies) == 1
    assert _count_primitive(bodies[0], "pallas_call") == 1
