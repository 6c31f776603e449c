"""Pallas TPU kernel: the entire ε-scaling auction fused into one call.

The legacy matcher path (``core.jaxopt.matching.match_auction``) runs the
bidding loop as a ``lax.while_loop`` *around* a Pallas top-2 reduction, so
every round round-trips through XLA and re-materializes whole-matrix
intermediates. This kernel owns the loop instead:

* **grid = (num_phases,)** — the ε-scaling phase axis; the ε schedule sits
  in SMEM, and so does the output that counts each phase's bidding rounds. Column dual prices live in the resident price output block and
  persist across grid steps (seeded from the warm-start input on phase 0);
  each phase restarts the assignment maps and bids until complete, exactly
  the ε-scaling restart semantics of the registry matchers.
* **in-kernel bidding rounds** — bid → price-update → assignment-flip runs
  inside a ``lax.while_loop`` *within* the kernel, so rounds never leave
  VMEM and never re-dispatch.
* **blocked/tiled** — both the per-row top-2 bid reduction and the
  per-column winner selection run a ``fori_loop`` over lane-aligned
  ``block_cols``-wide tiles (the row dimension is processed whole; padding
  keeps it sublane-aligned), bounding VMEM temporaries at one tile's
  (n_pad × block_cols) set so n = 1024 fits the default scoped VMEM limit
  beside the resident benefit matrix (4 MB f32). An unrolled Python loop
  over the tiles kept every tile's temporaries live: 17 MB at n = 1024.
* **2-D layouts only** — per-row state is ``(n_pad, 1)`` and per-column
  state ``(1, n_pad)``, so row reductions stay on lanes and column
  reductions on sublanes with no transpose, no 1-D relayout and no
  ``dynamic_update_slice`` (tiles are stored into the output refs), which
  is what the Mosaic TPU lowering accepts. The argmax is written as the
  lowest column index attaining the max.

Round semantics (shared bit-for-bit with ``ref.fused_auction_ref`` — the
interpret-mode parity tests assert exact equality):

1. every unassigned row computes its top-2 values ``(v1, v2)`` of
   ``W − prices`` and bids ``inc = v1 − v2 + ε`` on its favorite column
   (ties → lowest column index, merged first-tile-wins across tiles);
2. each column takes the highest bid (ties → lowest row index), kicks its
   previous owner, and raises its price by the winning increment — the
   increment formulation avoids gathers: every bidder on column j shares
   ``prices[j]``, so comparing increments IS comparing absolute bids;
3. row→column assignments are rebuilt from the column→row map (a row bids
   only while unassigned, so the map stays injective).

Padding contract (see ``ops.fused_auction``): padded columns carry ``NEG``
weight so no real row ever bids on them; padded rows start each phase
assigned to their own padded column, so the termination count of unassigned
rows only watches real rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
NEG_HALF = NEG / 2


def _fused_auction_kernel(
    eps_ref,      # SMEM (1, num_phases) f32 — the ε schedule
    W_ref,        # (n_pad, n_pad) f32 — benefit matrix (NEG-padded)
    p0_ref,       # (1, n_pad) f32 — warm-start column prices
    r2c_ref,      # out (n_pad, 1) i32 — row → column, per-row column layout
    c2r_ref,      # out (1, n_pad) i32 — column → row, per-column row layout
    price_ref,    # out (1, n_pad) f32 — column prices, carried across phases
    iters_ref,    # out SMEM (1, num_phases) i32 — bidding rounds per phase
    *,
    n: int,
    n_pad: int,
    block_cols: int,
    max_iters: int,
):
    # The outputs have the same block index on every grid step, so they
    # stay resident in VMEM across the ε phases and double as loop state.
    pid = pl.program_id(0)

    @pl.when(pid == 0)
    def _seed_prices():
        price_ref[...] = p0_ref[...]

    eps = eps_ref[0, pid]
    nt = n_pad // block_cols
    rows2d = jax.lax.broadcasted_iota(jnp.int32, (n_pad, block_cols), 0)
    cols2d = jax.lax.broadcasted_iota(jnp.int32, (n_pad, block_cols), 1)
    # Each phase restarts from "real rows/columns unassigned, padding
    # matched to itself" (the padding contract above).
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (n_pad, 1), 0)
    col_ids = jax.lax.broadcasted_iota(jnp.int32, (1, n_pad), 1)
    r2c_ref[...] = jnp.where(row_ids < n, -1, row_ids)
    c2r_ref[...] = jnp.where(col_ids < n, -1, col_ids)

    def cond(carry):
        it, active = carry
        return (active > 0) & (it < max_iters)

    def tile_cols(ct):
        return pl.ds(pl.multiple_of(ct * block_cols, block_cols), block_cols)

    def bid_tile(ct, acc):
        v1, v2, j1 = acc
        cs = tile_cols(ct)
        tile = W_ref[:, cs] - price_ref[:, cs]
        t1 = tile.max(axis=1, keepdims=True)
        # first column attaining the max (= argmax's tie-break)
        jloc = jnp.where(tile == t1, cols2d, block_cols).min(
            axis=1, keepdims=True
        )
        t2 = jnp.where(cols2d == jloc, NEG, tile).max(axis=1, keepdims=True)
        take = t1 > v1  # strict: earlier tile wins ties = global argmax
        v2 = jnp.where(take, jnp.maximum(t2, v1), jnp.maximum(v2, t1))
        v1 = jnp.where(take, t1, v1)
        j1 = jnp.where(take, jloc + ct * block_cols, j1)
        return v1, v2, j1

    def body(carry):
        it, _ = carry
        r2c = r2c_ref[...]

        # ---- bid: per-row top-2 of W − prices, blocked over column tiles.
        v1, v2, j1 = jax.lax.fori_loop(
            0,
            nt,
            bid_tile,
            (
                jnp.full((n_pad, 1), NEG, jnp.float32),
                jnp.full((n_pad, 1), NEG, jnp.float32),
                jnp.zeros((n_pad, 1), jnp.int32),
            ),
        )
        inc = jnp.where(r2c < 0, v1 - v2 + eps, NEG)

        # ---- price-update + assignment-flip, blocked over column tiles.
        def update_tile(ct, r2c_acc):
            cs = tile_cols(ct)
            cols_g = cols2d + ct * block_cols
            contrib = jnp.where(j1 == cols_g, inc, NEG)
            best = contrib.max(axis=0, keepdims=True)        # (1, bc)
            cand = (contrib >= best) & (contrib > NEG_HALF)
            winner = jnp.where(cand, rows2d, n_pad).min(axis=0, keepdims=True)
            has = winner < n_pad
            c2r_t = jnp.where(has, winner, c2r_ref[:, cs])
            p_t = price_ref[:, cs]
            price_ref[:, cs] = jnp.where(has, p_t + best, p_t)
            c2r_ref[:, cs] = c2r_t
            # Row i owns column ct*block_cols + j iff c2r_t[j] == i (the map
            # is injective).
            owned = c2r_t == rows2d
            return jnp.minimum(
                r2c_acc,
                jnp.where(owned, cols_g, n_pad).min(axis=1, keepdims=True),
            )

        r2c_acc = jax.lax.fori_loop(
            0, nt, update_tile, jnp.full((n_pad, 1), n_pad, jnp.int32)
        )
        new_r2c = jnp.where(r2c_acc < n_pad, r2c_acc, -1)
        r2c_ref[...] = new_r2c
        unassigned = jnp.sum((new_r2c < 0).astype(jnp.int32))
        return it + 1, unassigned

    # n ≥ 1 real row starts unassigned, so the first round always runs.
    it, _ = jax.lax.while_loop(cond, body, (jnp.int32(0), jnp.int32(1)))
    iters_ref[0, pid] = it


@functools.partial(
    jax.jit, static_argnames=("n", "block_cols", "max_iters", "interpret")
)
def fused_auction_pallas(
    W: jax.Array,             # (n_pad, n_pad), NEG-padded, n_pad % 128 == 0
    prices0: jax.Array,       # (1, n_pad)
    eps_schedule: jax.Array,  # (1, num_phases)
    *,
    n: int,
    block_cols: int,
    max_iters: int,
    interpret: bool = False,
):
    """Returns ``(r2c (n_pad, 1), c2r (1, n_pad), prices (1, n_pad),
    iters (1, num_phases))``; rows/columns ``>= n`` are padding, and
    ``iters`` holds the bidding rounds of each phase."""
    n_pad = W.shape[0]
    if n_pad % block_cols:
        raise ValueError(
            f"padded size {n_pad} not divisible by block_cols {block_cols}"
        )
    num_phases = eps_schedule.shape[1]
    kernel = functools.partial(
        _fused_auction_kernel,
        n=n,
        n_pad=n_pad,
        block_cols=block_cols,
        max_iters=max_iters,
    )
    row_spec = pl.BlockSpec((1, n_pad), lambda p: (0, 0))
    return pl.pallas_call(
        kernel,
        grid=(num_phases,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((n_pad, n_pad), lambda p: (0, 0)),
            row_spec,
        ],
        out_specs=(
            pl.BlockSpec((n_pad, 1), lambda p: (0, 0)),
            row_spec,
            row_spec,
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n_pad), W.dtype),
            jax.ShapeDtypeStruct((1, num_phases), jnp.int32),
        ),
        interpret=interpret,
    )(eps_schedule, W, prices0)
