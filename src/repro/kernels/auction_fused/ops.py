"""Dispatch wrapper for the fused auction: pads, tiles, picks kernel vs ref.

``fused_auction`` is the one entry point the matcher registry calls. It
pads the benefit matrix to lane-aligned 128-multiples (NEG columns, with
padded rows pre-assigned to padded columns — see kernel.py's padding
contract), chooses the column tile width (whole matrix below 256, 128-wide
lane tiles at and above so VMEM temporaries stay bounded), and runs the
Pallas kernel — compiled on TPU, interpret mode elsewhere — or, with
``use_kernel=False``, the exactly-matching jnp reference at the original
(unpadded) shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..backend import on_tpu
from .kernel import NEG, fused_auction_pallas
from .ref import fused_auction_ref

# Lane-aligned tile width; also the padding quantum. Below this the whole
# (padded) matrix is one tile.
_LANE = 128


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_iters", "use_kernel", "block_cols", "interpret", "with_iters"
    ),
)
def fused_auction(
    W: jax.Array,
    prices0: jax.Array,
    eps_schedule: jax.Array,
    *,
    max_iters: int,
    use_kernel: bool = True,
    block_cols: int | None = None,
    interpret: bool | None = None,
    with_iters: bool = False,
):
    """Run the fused ε-scaling auction; returns ``(r2c, c2r, prices)`` at
    the caller's (unpadded) n. ``interpret=None`` → auto (off on TPU).

    ``with_iters=True`` appends the bidding rounds of each ε phase,
    ``(num_phases,)`` int32; the kernel and the reference count the same
    rounds.
    """
    if not use_kernel:
        return fused_auction_ref(
            W, prices0, eps_schedule, max_iters=max_iters,
            with_iters=with_iters,
        )
    if interpret is None:
        interpret = not on_tpu()
    n = W.shape[0]
    n_pad = max(_LANE, -(-n // _LANE) * _LANE)
    pad = n_pad - n
    Wp = jnp.pad(
        W.astype(jnp.float32), ((0, pad), (0, pad)), constant_values=NEG
    )
    p0 = jnp.pad(jnp.asarray(prices0, jnp.float32), (0, pad))[None, :]
    if block_cols is None:
        block_cols = _LANE if n_pad >= 256 else n_pad
    r2c, c2r, prices, iters = fused_auction_pallas(
        Wp,
        p0,
        jnp.asarray(eps_schedule, jnp.float32)[None, :],
        n=n,
        block_cols=block_cols,
        max_iters=max_iters,
        interpret=bool(interpret),
    )
    r2c, c2r, prices = r2c[:, 0], c2r[0], prices[0]
    if with_iters:
        return r2c[:n], c2r[:n], prices[:n], iters[0]
    return r2c[:n], c2r[:n], prices[:n]
