"""Fused DECOMPOSE → SCHEDULE → EQUALIZE on device: one jitted, vmappable call.

``spectra_jax_e2e`` chains the ε-scaling auction decomposition (Alg. 1+2),
device LPT (Alg. 3), and the ``lax.while_loop`` EQUALIZE (Alg. 4) into a
single XLA program emitting a dense ``DeviceSchedule``; ``spectra_jax_e2e_many``
is its ``vmap`` over stacked demand matrices — the controller path that
re-solves scheduling for many concurrent demand matrices per period without
a host round-trip between stages.

Each stage runs under a ``jax.named_scope`` from ``repro.obs.device.SCOPES``
(``spectra.decompose.*``, ``spectra.lpt``, ``spectra.equalize``,
``spectra.lb``), so a device trace can be split by stage.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..schedule_ir import DeviceSchedule
from .decompose_jax import JaxDecomposition, decompose_jax, lpt_schedule_jax
from .equalize_jax import device_loads, equalize_ir
from .lower_bounds_jax import lower_bound_jax


class E2EResult(NamedTuple):
    """Device-resident result of the fused pipeline (one instance per lane)."""

    schedule: DeviceSchedule      # post-EQUALIZE slot table
    dec: JaxDecomposition         # raw DECOMPOSE output (pre-EQUALIZE weights)
    makespan: jax.Array           # () float32 — max switch load after EQUALIZE
    lpt_makespan: jax.Array       # () float32 — Alg. 3 makespan before EQUALIZE
    eq_exhausted: jax.Array       # () bool — EQUALIZE ran out of split slots
                                  # (raise extra_slots; host parity not reached)
    lb: jax.Array                 # () float32 — §IV lower bound of the instance


def _ir_makespan(ds: DeviceSchedule, s: int) -> jax.Array:
    return device_loads(ds.alphas, ds.switch, ds.delta, s).max()


@functools.partial(
    jax.jit,
    static_argnames=(
        "s", "use_kernel", "do_equalize", "merge_aware", "extra_slots",
        "matcher", "repair_rounds", "axis_name",
    ),
)
def spectra_jax_e2e(
    D: jax.Array,
    s: int,
    delta,
    *,
    use_kernel: bool = False,
    do_equalize: bool = True,
    merge_aware: bool = False,
    extra_slots: int = 64,
    matcher: str = "auction",
    repair_rounds: int = 0,
    axis_name: str | None = None,
) -> E2EResult:
    """Full SPECTRA pipeline for one (n, n) demand matrix, entirely on device.

    ``extra_slots`` is the EQUALIZE split headroom appended to the n
    decomposition slots (each non-merging split consumes one slot).
    ``matcher`` selects the device MWM solver (``matching.MATCHERS``);
    ``repair_rounds`` bounds the post-REFINE local-search sweeps;
    ``axis_name`` names the ``vmap`` axis when batched (it feeds
    ``dec.bid_rounds_run``).
    """
    D = jnp.asarray(D, jnp.float32)
    n = D.shape[0]
    delta = jnp.asarray(delta, jnp.float32)
    dec = decompose_jax(
        D, use_kernel=use_kernel, matcher=matcher,
        repair_rounds=repair_rounds, axis_name=axis_name,
    )
    with jax.named_scope("spectra.lpt"):
        assignment, _, lpt_makespan = lpt_schedule_jax(dec, s, delta)
    pad_perms = jnp.broadcast_to(
        jnp.arange(n, dtype=jnp.int32)[None, :], (extra_slots, n)
    )
    ds = DeviceSchedule(
        perms=jnp.concatenate([dec.perms, pad_perms], axis=0),
        alphas=jnp.concatenate([dec.alphas, jnp.zeros((extra_slots,), jnp.float32)]),
        switch=jnp.concatenate(
            [assignment, jnp.full((extra_slots,), -1, jnp.int32)]
        ),
        delta=delta,
    )
    eq_exhausted = jnp.bool_(False)
    if do_equalize:
        with jax.named_scope("spectra.equalize"):
            ds, eq_exhausted = equalize_ir(ds, s, merge_aware=merge_aware)
    with jax.named_scope("spectra.lb"):
        lb = lower_bound_jax(D, s, delta)
    return E2EResult(
        schedule=ds,
        dec=dec,
        makespan=_ir_makespan(ds, s),
        lpt_makespan=lpt_makespan,
        eq_exhausted=eq_exhausted,
        lb=lb,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "s", "use_kernel", "do_equalize", "merge_aware", "extra_slots",
        "matcher", "repair_rounds",
    ),
)
def spectra_jax_e2e_many(
    Ds: jax.Array,
    s: int,
    delta,
    *,
    use_kernel: bool = False,
    do_equalize: bool = True,
    merge_aware: bool = False,
    extra_slots: int = 64,
    matcher: str = "auction",
    repair_rounds: int = 0,
) -> E2EResult:
    """vmapped fused pipeline over stacked (B, n, n) demand matrices.

    ``delta`` may be a scalar (one δ for the whole batch) or a (B,) vector
    (per-instance δ — how trace-aware δ sweeps batch a whole trace whose
    reconfiguration delay varies per period into one dispatch).
    """
    axis = "batch"
    Ds = jnp.asarray(Ds, jnp.float32)
    deltas = jnp.broadcast_to(
        jnp.asarray(delta, jnp.float32), (Ds.shape[0],)
    )
    return jax.vmap(
        lambda D, d: spectra_jax_e2e(
            D,
            s,
            d,
            use_kernel=use_kernel,
            do_equalize=do_equalize,
            merge_aware=merge_aware,
            extra_slots=extra_slots,
            matcher=matcher,
            repair_rounds=repair_rounds,
            axis_name=axis,
        ),
        axis_name=axis,
    )(Ds, deltas)
