"""Pure-jnp oracle for the fused auction kernel — and the fast host path.

Implements exactly the round semantics documented in ``kernel.py`` (same
bid/increment formulas, same first-index tie-breaks, same float evaluation
order), so interpret-mode kernel runs compare *bit-exactly* against it.

It is also the performant matcher on non-TPU backends: where the legacy
``match_auction`` round materializes a dense (n, n) scatter matrix to find
each column's best bid (three O(n²) passes per round beyond the top-2
reduction), this round uses O(n) segment scatters — ``.at[j1].max`` for the
winning increment, ``.at[...].min`` for the winning row — so each round
costs one O(n²) pass (the unavoidable ``W − prices`` top-2) plus O(n)
bookkeeping. That is where the measured ≥1.5× per-dispatch speedup at
n ≥ 256 comes from on CPU hosts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG = -1e30
NEG_HALF = NEG / 2


def _round(W, r2c, c2r, prices, eps, rows, cols):
    """One Jacobi bidding round; see kernel.py for the shared semantics."""
    n = W.shape[0]
    V = W - prices[None, :]
    j1 = jnp.argmax(V, axis=1).astype(jnp.int32)
    v1 = jnp.take_along_axis(V, j1[:, None].astype(jnp.int32), axis=1)[:, 0]
    v2 = jnp.where(cols[None, :] == j1[:, None], NEG, V).max(axis=1)
    inc = jnp.where(r2c < 0, v1 - v2 + eps, NEG)
    # Columns take the best increment (all bidders on j share prices[j], so
    # comparing increments is comparing absolute bids); winner = lowest row.
    col_inc = jnp.full((n,), NEG, W.dtype).at[j1].max(inc)
    cand = (inc > NEG_HALF) & (inc >= col_inc[j1])
    winner = (
        jnp.full((n,), n, jnp.int32)
        .at[jnp.where(cand, j1, n)]
        .min(rows, mode="drop")
    )
    has = winner < n
    c2r = jnp.where(has, winner, c2r)
    prices = jnp.where(has, prices + col_inc, prices)
    # Rebuild row→col from the (injective) col→row map.
    r2c = (
        jnp.full((n,), -1, jnp.int32)
        .at[jnp.where(c2r >= 0, c2r, n)]
        .set(cols, mode="drop")
    )
    return r2c, c2r, prices


@functools.partial(jax.jit, static_argnames=("max_iters", "with_iters"))
def fused_auction_ref(
    W: jax.Array,
    prices0: jax.Array,
    eps_schedule: jax.Array,
    *,
    max_iters: int,
    with_iters: bool = False,
):
    """ε-scaling auction over ``eps_schedule``; returns (r2c, c2r, prices).

    Each phase restarts the assignment maps from scratch but keeps the
    learned prices — identical to the kernel's per-phase grid steps.
    ``with_iters=True`` appends the bidding rounds of each phase, counted
    as the kernel counts them — the convergence-cost observable
    warm-started prices reduce.
    """
    W = W.astype(jnp.float32)
    n = W.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    cols = rows

    def phase(state, eps):
        _, _, prices = state

        def cond(c):
            r2c, _, _, it = c
            return (r2c < 0).any() & (it < max_iters)

        def body(c):
            r2c, c2r, prices, it = c
            r2c, c2r, prices = _round(W, r2c, c2r, prices, eps, rows, cols)
            return r2c, c2r, prices, it + 1

        r2c, c2r, prices, it = jax.lax.while_loop(
            cond,
            body,
            (
                jnp.full((n,), -1, jnp.int32),
                jnp.full((n,), -1, jnp.int32),
                prices,
                jnp.int32(0),
            ),
        )
        return (r2c, c2r, prices), it

    state = (
        jnp.full((n,), -1, jnp.int32),
        jnp.full((n,), -1, jnp.int32),
        jnp.asarray(prices0, jnp.float32),
    )
    (r2c, c2r, prices), phase_iters = jax.lax.scan(phase, state, eps_schedule)
    if with_iters:
        return r2c, c2r, prices, phase_iters
    return r2c, c2r, prices
