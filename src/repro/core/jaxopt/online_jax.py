"""Online (cross-period) SPECTRA on device: stateful steps and a rolling scan.

The stateless pipeline re-pays the reconfiguration delay δ for every
configuration every controller period. AI training traffic is heavily
periodic, so consecutive periods reuse most permutations — an online
controller that remembers each switch's *installed* permutation can serve a
matching configuration first with **zero** δ (reuse credit) and warm-start
the next period's decomposition from the previous one.

State carried across periods (``OnlineDeviceState``):

    installed   (s, n) int32   permutation left installed on each switch at
                               the end of the previous period (-1 row: never
                               configured)
    prev_perms  (n, n) int32   previous period's decomposition permutations
                               (warm-start seed), live rounds packed first
    prev_k      ()     int32   number of live previous rounds
    prices      (n,)   float32 matcher dual-price carry (see ``matching``)
    fresh_ratio ()     float32 tightest fresh-decomposition weight ratio
                               observed — the warm-quality gate reference
    cache_*     (C,…)          device-resident support-pattern cache (the
                               host controller's ``support_cache`` in the
                               scan carry): memoized supports, perm sets,
                               live counts, quality references, and a
                               round-robin eviction cursor; C=0 disables

The warm tiers mirror the host controller: the previous period's set is
tried first (adjacency), then — only if that fails — the support-pattern
cache is probed with this period's exact support, serving phase-cycling
traffic (e.g. MoE routing phases) without re-decomposing.

Per-period algorithm (``online_step_jax``):

1. **Warm-start decomposition** — re-REFINE the previous period's
   permutation set against the new demand (one greedy pass, no matching
   solves). If it covers the new support AND passes the quality gate (round
   count ≤ degree(D); scale-free weight ratio within ``warm_slack`` of the
   tightest fresh decomposition observed — coverage alone doesn't bound
   quality when weights drift), the expensive auction DECOMPOSE is skipped
   entirely (``lax.cond``); otherwise a fresh device decomposition runs
   (optionally warm-starting the auction's dual prices from the carry).
2. **Reuse-then-LPT** — each switch greedily claims a round whose
   permutation equals its installed configuration (serving it first, δ-free),
   then the remaining rounds are placed by plain LPT.
3. **Credit-aware EQUALIZE** — Alg. 4 over the slot table with a −δ load
   offset on every switch holding a carried configuration.
4. **Best-of selection** — the stateless candidate (plain LPT + uncredited
   EQUALIZE of the *same* decomposition) is always computed too; applying
   the reuse credit to it post-hoc is free, so the chosen schedule's
   effective makespan is ≤ the *same-decomposition* stateless makespan by
   construction. (``run_scenario`` additionally clamps every period
   against the independently solved TRUE stateless baseline on the host —
   see ``repro.scenarios.runner``.)
5. **State update** — each switch's new installed permutation is the last
   configuration it serves (slot-index order, reused config first, EQUALIZE
   splits last).

Stages run under the ``jax.named_scope`` names of ``repro.obs.device``:
the stateless pipeline's where the stage is the same (``spectra.lpt``,
``spectra.equalize``, ``spectra.lb`` and DECOMPOSE's), and
``online.warm_refine``, ``online.reuse_lpt`` and ``online.credit``.

``spectra_online_scan`` rolls the step over a whole (T, n, n) trace under
``lax.scan`` with the switch state as carry: an entire training run's
scheduling is ONE device dispatch, no host round-trips between periods.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..schedule_ir import DeviceSchedule
from .decompose_jax import (
    JaxDecomposition,
    _decompose,
    lpt_schedule_jax,
)
from .equalize_jax import device_loads, equalize_ir
from .lower_bounds_jax import lower_bound_jax


class OnlineDeviceState(NamedTuple):
    """Cross-period carry of the online controller (see module doc)."""

    installed: jax.Array   # (s, n) int32; -1 row = unconfigured switch
    prev_perms: jax.Array  # (n, n) int32; previous decomposition, packed
    prev_k: jax.Array      # () int32; live previous rounds
    prices: jax.Array      # (n,) float32; matcher dual-price carry
    fresh_ratio: jax.Array  # () float32; last FRESH dec's Σα / max-line-sum
                            # — the warm-acceptance quality reference
    # Device-resident support-pattern cache (the host controller's
    # ``SwitchState.support_cache`` moved into the scan carry). Capacity C
    # is a *shape* — it travels with the state, so jitted steps need no
    # extra static argument; C=0 disables the cache with zero-size arrays.
    cache_supports: jax.Array  # (C, n, n) bool — memoized support patterns
    cache_perms: jax.Array     # (C, n, n) int32 — each pattern's perm set
    cache_k: jax.Array         # (C,) int32 — live rounds per entry (0=empty)
    cache_ratio: jax.Array     # (C,) float32 — quality reference at insert
    cache_ptr: jax.Array       # () int32 — round-robin eviction cursor


class OnlineStepResult(NamedTuple):
    """One period's device-resident online outcome."""

    schedule: DeviceSchedule       # chosen slot table (credit-aware)
    reused: jax.Array              # (R,) bool — slots served δ-free
    makespan: jax.Array            # () float32 — credit-aware makespan
    stateless_makespan: jax.Array  # () float32 — same-dec uncredited makespan
    reuse_count: jax.Array         # () int32 — switches with a carried config
    warm: jax.Array                # () bool — warm-start decomposition used
    lb: jax.Array                  # () float32 — §IV (stateless) lower bound
    k: jax.Array                   # () int32 — decomposition rounds
    converged: jax.Array           # () bool — matcher convergence
    eq_exhausted: jax.Array        # () bool — EQUALIZE headroom exhausted
    cache_hit: jax.Array           # () bool — warm came from the support cache


def online_initial_state(
    n: int, s: int, cache_size: int = 0
) -> OnlineDeviceState:
    """Fresh controller state: no configurations installed anywhere.

    ``cache_size`` sizes the device-resident support-pattern cache carried
    with the state (0 = disabled — the pre-cache state shape, and the
    default for raw steps; the serving layer and ``run_scenario`` opt in)."""
    identity = jnp.arange(n, dtype=jnp.int32)[None, :]
    return OnlineDeviceState(
        installed=jnp.full((s, n), -1, jnp.int32),
        prev_perms=jnp.broadcast_to(identity, (n, n)),
        prev_k=jnp.int32(0),
        prices=jnp.zeros((n,), jnp.float32),
        # +inf = "no fresh reference yet"; harmless because warm-start
        # cannot trigger before the first (necessarily fresh) period.
        fresh_ratio=jnp.float32(jnp.inf),
        cache_supports=jnp.zeros((cache_size, n, n), bool),
        cache_perms=jnp.broadcast_to(identity[None], (cache_size, n, n)),
        cache_k=jnp.zeros((cache_size,), jnp.int32),
        cache_ratio=jnp.full((cache_size,), jnp.inf, jnp.float32),
        cache_ptr=jnp.int32(0),
    )


def _warm_refine(D: jax.Array, perms: jax.Array, k: jax.Array):
    """Greedy REFINE of ``D`` along a *given* permutation set (weights from
    zero). Returns ``(alphas, residual)`` — residual is the demand no
    permutation in the set can serve."""
    n = D.shape[0]
    arange = jnp.arange(n)

    def body(r, carry):
        R, alphas = carry
        perm = perms[r]
        d = jnp.maximum(R[arange, perm].max(), 0.0)
        d = jnp.where(r < k, d, 0.0)
        alphas = alphas.at[r].set(d)
        R = jnp.maximum(R.at[arange, perm].add(-d), 0.0)
        return R, alphas

    R, alphas = jax.lax.fori_loop(
        0, n, body, (D, jnp.zeros((n,), jnp.float32))
    )
    return alphas, R


def _switch_credit(
    perms: jax.Array,
    switch: jax.Array,
    installed: jax.Array,
    s: int,
):
    """Per-switch reuse marks on a slot table.

    Returns ``(reused (R,) bool, has (s,) bool)``: at most one live slot per
    switch (the first, by slot index) whose permutation equals that switch's
    installed configuration — the slot the switch can serve δ-free.
    """
    R = switch.shape[0]
    live = switch >= 0
    inst_valid = installed[:, 0] >= 0
    arange = jnp.arange(R)
    reused = jnp.zeros((R,), bool)
    has = []
    for h in range(s):
        m = (
            live
            & (switch == h)
            & inst_valid[h]
            & (perms == installed[h][None, :]).all(axis=-1)
        )
        hit = m.any()
        reused = reused | (hit & (arange == jnp.argmax(m)))
        has.append(hit)
    return reused, jnp.stack(has)


def _reuse_then_lpt(
    dec: JaxDecomposition,
    installed: jax.Array,
    s: int,
    delta: jax.Array,
):
    """Reuse-aware Alg. 3: each switch first claims a round matching its
    installed permutation (no δ), the rest is plain LPT on the credited
    loads. Returns ``(assignment (n,), reused_rounds (n,) bool)``."""
    n = dec.alphas.shape[0]
    arange = jnp.arange(n)
    valid = (arange < dec.k) & (dec.alphas > 0)
    inst_valid = installed[:, 0] >= 0

    taken = jnp.zeros((n,), bool)
    assignment = jnp.full((n,), -1, jnp.int32)
    loads = jnp.zeros((s,), jnp.float32)
    for h in range(s):
        m = (
            valid
            & ~taken
            & inst_valid[h]
            & (dec.perms == installed[h][None, :]).all(axis=-1)
        )
        hit = m.any()
        r = jnp.argmax(m)
        sel = hit & (arange == r)
        taken = taken | sel
        assignment = jnp.where(sel, h, assignment)
        loads = loads.at[h].add(jnp.where(hit, dec.alphas[r], 0.0))
    reused_rounds = taken

    remaining = valid & ~taken
    order = jnp.argsort(jnp.where(remaining, -dec.alphas, jnp.inf))

    def place(loads, idx):
        a = dec.alphas[idx]
        is_real = jnp.take(remaining, idx)
        h = jnp.argmin(loads)
        loads = jnp.where(is_real, loads.at[h].add(delta + a), loads)
        return loads, jnp.where(is_real, h, -1)

    loads, placed = jax.lax.scan(place, loads, order)
    assignment = jnp.where(
        remaining,
        jnp.full((n,), -1, jnp.int32).at[order].set(placed.astype(jnp.int32)),
        assignment,
    )
    return assignment, reused_rounds


def _build_table(dec, assignment, delta, extra_slots: int) -> DeviceSchedule:
    n = dec.perms.shape[-1]
    pad_perms = jnp.broadcast_to(
        jnp.arange(n, dtype=jnp.int32)[None, :], (extra_slots, n)
    )
    return DeviceSchedule(
        perms=jnp.concatenate([dec.perms, pad_perms], axis=0),
        alphas=jnp.concatenate(
            [dec.alphas, jnp.zeros((extra_slots,), jnp.float32)]
        ),
        switch=jnp.concatenate(
            [assignment, jnp.full((extra_slots,), -1, jnp.int32)]
        ),
        delta=delta,
    )


def _credited_makespan(ds: DeviceSchedule, installed, s: int, delta):
    """(makespan, reused marks, per-switch credit flags) of a final table."""
    reused, has = _switch_credit(ds.perms, ds.switch, installed, s)
    loads = device_loads(ds.alphas, ds.switch, delta, s) - delta * has
    return loads.max(), reused, has


def _last_served(ds: DeviceSchedule, reused, installed, s: int) -> jax.Array:
    """New installed state: the last configuration each switch serves.

    Serve order is slot-index order with the reused slot moved first, so
    the last non-reused live slot (EQUALIZE splits sit at the highest
    indices) is what remains installed; a switch serving only its carried
    configuration — or nothing — keeps its previous state.
    """
    R = ds.switch.shape[0]
    live = ds.switch >= 0
    idx = jnp.arange(R)
    rows = []
    for h in range(s):
        nr = live & (ds.switch == h) & ~reused
        last = jnp.max(jnp.where(nr, idx, -1))
        rows.append(
            jnp.where(nr.any(), ds.perms[jnp.maximum(last, 0)], installed[h])
        )
    return jnp.stack(rows)


def _online_step(
    state: OnlineDeviceState,
    D: jax.Array,
    s: int,
    delta,
    *,
    use_kernel: bool,
    do_equalize: bool,
    merge_aware: bool,
    extra_slots: int,
    matcher: str,
    repair_rounds: int,
    warm_start: bool,
    warm_prices: bool,
    warm_slack: float,
) -> tuple[OnlineStepResult, OnlineDeviceState]:
    D = jnp.asarray(D, jnp.float32)
    n = D.shape[0]
    delta = jnp.asarray(delta, jnp.float32)
    line_sum = jnp.maximum(D.sum(axis=0).max(), D.sum(axis=1).max())
    line_sum_safe = jnp.maximum(line_sum, 1e-30)

    # ---- 1. decomposition: warm (re-REFINE previous set) or fresh --------
    def fresh(op):
        D_, prices_ = op
        dec_, prices_out = _decompose(
            D_,
            use_kernel=use_kernel,
            matcher=matcher,
            repair_rounds=repair_rounds,
            carry_prices=warm_prices,
            prices0=prices_ if warm_prices else None,
        )
        return dec_, prices_out if warm_prices else prices_

    S = D > 0
    deg = jnp.maximum(S.sum(axis=0).max(), S.sum(axis=1).max())
    cache_size = state.cache_supports.shape[0]

    def try_warm(perms, k, ref_ratio):
        """Re-REFINE ``D`` along a candidate permutation set; returns the
        packed decomposition plus its coverage/quality acceptance.

        Quality gate: re-REFINE along a stale permutation set can badly
        over-provision when weights drift (coverage alone doesn't bound
        it). Σα / max-line-sum is scale-free and ≥ 1 for any cover, so
        comparing against the reference FRESH decomposition's ratio bounds
        the warm excess to ``warm_slack``; the round count may not exceed
        degree(D) (a fresh decomposition's exact k) either.
        """
        with jax.named_scope("online.warm_refine"):
            alphas_w, residual = _warm_refine(D, perms, k)
            covered = residual.max() <= 1e-5 * jnp.maximum(D.max(), 1e-30)
            live = alphas_w > 0
            order = jnp.argsort(~live, stable=True)
            dec_ = JaxDecomposition(
                perms=perms[order],
                alphas=jnp.where(live, alphas_w, 0.0)[order],
                k=live.sum().astype(jnp.int32),
                converged=jnp.bool_(True),
                bid_rounds=jnp.int32(0),
                bid_rounds_run=jnp.int32(0),
            )
            warm_ratio = alphas_w.sum() / line_sum_safe
            quality_ok = (dec_.k <= deg) & (
                warm_ratio <= ref_ratio * (1.0 + warm_slack)
            )
        return dec_, covered & quality_ok

    if warm_start:
        warm_dec, adj_ok = try_warm(
            state.prev_perms, state.prev_k, state.fresh_ratio
        )
        use_adj = adj_ok & (state.prev_k > 0)
        if cache_size:
            # Support-pattern cache tier: consulted only when the adjacency
            # warm start fails — the exact lookup order of the host
            # controller. An entry matches when its memoized support equals
            # this period's (and is live); its perm set then re-REFINEs
            # under the same coverage/quality gates, referenced against the
            # quality ratio memoized at insert time.
            match = (
                (state.cache_supports == S[None]).all(axis=(1, 2))
                & (state.cache_k > 0)
            )
            hit = match.any()
            slot = jnp.argmax(match)
            cache_dec, cache_ok = try_warm(
                state.cache_perms[slot],
                jnp.where(hit, state.cache_k[slot], 0),
                state.cache_ratio[slot],
            )
            use_cache = ~use_adj & hit & cache_ok
        else:
            cache_dec, use_cache = warm_dec, jnp.bool_(False)
        use_warm = use_adj | use_cache
        warm_pick = jax.tree_util.tree_map(
            lambda a, c: jnp.where(use_adj, a, c), warm_dec, cache_dec
        )
        dec, prices = jax.lax.cond(
            use_warm,
            lambda op: (warm_pick, op[1]),
            fresh,
            (D, state.prices),
        )
    else:
        use_warm = use_cache = jnp.bool_(False)
        dec, prices = fresh((D, state.prices))

    # ---- 2+3. two candidates over the same decomposition -----------------
    # A: plain LPT + uncredited EQUALIZE — the stateless reference.
    with jax.named_scope("spectra.lpt"):
        assignment_a, _, _ = lpt_schedule_jax(dec, s, delta)
    ds_a = _build_table(dec, assignment_a, delta, extra_slots)
    # B: reuse-then-LPT + EQUALIZE on credited loads.
    with jax.named_scope("online.reuse_lpt"):
        assignment_b, reused_rounds = _reuse_then_lpt(
            dec, state.installed, s, delta
        )
    ds_b = _build_table(dec, assignment_b, delta, extra_slots)
    with jax.named_scope("online.credit"):
        _, has_b = _switch_credit(
            ds_b.perms, ds_b.switch, state.installed, s
        )
    ex_a = ex_b = jnp.bool_(False)
    if do_equalize:
        with jax.named_scope("spectra.equalize"):
            ds_a, ex_a = equalize_ir(ds_a, s, merge_aware=merge_aware)
            ds_b, ex_b = equalize_ir(
                ds_b, s, merge_aware=merge_aware, load_offset=-delta * has_b
            )

    # ---- 4. best-of selection (credit applied to both final tables) ------
    stateless_mk = device_loads(ds_a.alphas, ds_a.switch, delta, s).max()
    with jax.named_scope("online.credit"):
        mk_a, reused_a, has_a = _credited_makespan(
            ds_a, state.installed, s, delta
        )
        mk_b, reused_b, has_b_f = _credited_makespan(
            ds_b, state.installed, s, delta
        )
    use_b = mk_b <= mk_a
    ds = jax.tree_util.tree_map(
        lambda b, a: jnp.where(use_b, b, a), ds_b, ds_a
    )
    reused = jnp.where(use_b, reused_b, reused_a)
    makespan = jnp.minimum(mk_b, mk_a)
    reuse_count = jnp.where(use_b, has_b_f, has_a).sum().astype(jnp.int32)
    eq_exhausted = jnp.where(use_b, ex_b, ex_a)

    # ---- 5. state update --------------------------------------------------
    # The warm-quality reference ratchets only on FRESH periods, and only
    # DOWNWARD (running min): a warm period accepted at ref·(1+slack) must
    # never raise the bar, and the tightest fresh ratio ever observed is
    # the honest reference. Zero-demand periods (no line sum) leave it
    # untouched.
    new_fresh_ratio = jnp.where(
        use_warm | (line_sum <= 0),
        state.fresh_ratio,
        jnp.minimum(state.fresh_ratio, dec.alphas.sum() / line_sum_safe),
    )
    if cache_size:
        # Insert/update every period (the host controller's semantics):
        # a matching support slot is updated in place, otherwise the
        # round-robin cursor picks the eviction victim. Stored quality
        # reference is the post-ratchet fresh ratio, exactly what the host
        # memoizes alongside the perm set.
        ins_match = (state.cache_supports == S[None]).all(axis=(1, 2))
        ins_hit = ins_match.any()
        ins_slot = jnp.where(
            ins_hit, jnp.argmax(ins_match), state.cache_ptr % cache_size
        )
        cache_supports = state.cache_supports.at[ins_slot].set(S)
        cache_perms = state.cache_perms.at[ins_slot].set(dec.perms)
        cache_k = state.cache_k.at[ins_slot].set(dec.k)
        cache_ratio = state.cache_ratio.at[ins_slot].set(new_fresh_ratio)
        cache_ptr = jnp.where(ins_hit, state.cache_ptr, state.cache_ptr + 1)
    else:
        cache_supports = state.cache_supports
        cache_perms = state.cache_perms
        cache_k = state.cache_k
        cache_ratio = state.cache_ratio
        cache_ptr = state.cache_ptr
    new_state = OnlineDeviceState(
        installed=_last_served(ds, reused, state.installed, s),
        prev_perms=dec.perms,
        prev_k=dec.k,
        prices=prices,
        fresh_ratio=new_fresh_ratio,
        cache_supports=cache_supports,
        cache_perms=cache_perms,
        cache_k=cache_k,
        cache_ratio=cache_ratio,
        cache_ptr=cache_ptr,
    )
    with jax.named_scope("spectra.lb"):
        lb = lower_bound_jax(D, s, delta)
    result = OnlineStepResult(
        schedule=ds,
        reused=reused,
        makespan=makespan,
        stateless_makespan=stateless_mk,
        reuse_count=reuse_count,
        warm=use_warm,
        lb=lb,
        k=dec.k,
        converged=dec.converged,
        eq_exhausted=eq_exhausted,
        cache_hit=use_cache,
    )
    return result, new_state


_ONLINE_STATICS = (
    "s", "use_kernel", "do_equalize", "merge_aware", "extra_slots",
    "matcher", "repair_rounds", "warm_start", "warm_prices", "warm_slack",
)


@functools.partial(jax.jit, static_argnames=_ONLINE_STATICS)
def online_step_jax(
    state: OnlineDeviceState,
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
    warm_start: bool = True,
    warm_prices: bool = False,
    warm_slack: float = 0.05,
) -> tuple[OnlineStepResult, OnlineDeviceState]:
    """One stateful controller period on device; see module doc.

    The chosen schedule's credit-aware makespan is ≤ the same-decomposition
    stateless makespan by construction (the stateless candidate with the
    credit applied post-hoc is always in the running).
    """
    return _online_step(
        state, D, s, delta,
        use_kernel=use_kernel, do_equalize=do_equalize,
        merge_aware=merge_aware, extra_slots=extra_slots, matcher=matcher,
        repair_rounds=repair_rounds, warm_start=warm_start,
        warm_prices=warm_prices, warm_slack=warm_slack,
    )


@functools.partial(
    jax.jit, static_argnames=_ONLINE_STATICS + ("cache_size",)
)
def spectra_online_scan(
    Ds: jax.Array,
    s: int,
    deltas,
    *,
    use_kernel: bool = False,
    do_equalize: bool = True,
    merge_aware: bool = False,
    extra_slots: int = 64,
    matcher: str = "auction",
    repair_rounds: int = 0,
    warm_start: bool = True,
    warm_prices: bool = False,
    warm_slack: float = 0.05,
    cache_size: int = 0,
) -> tuple[OnlineStepResult, OnlineDeviceState]:
    """Roll the online step over a whole (T, n, n) trace in ONE dispatch.

    ``lax.scan`` over the T axis with the switch state as carry — the
    device-resident analogue of a controller loop, minus T-1 host
    round-trips. ``deltas`` is a scalar or a (T,) per-period δ vector.
    ``cache_size`` sizes the in-carry support-pattern cache (0 = off), the
    device analogue of the host controller's phase-cycling memoization.
    Returns the per-period results stacked over T plus the final state.
    """
    Ds = jnp.asarray(Ds, jnp.float32)
    T, n = Ds.shape[0], Ds.shape[1]
    deltas = jnp.broadcast_to(jnp.asarray(deltas, jnp.float32), (T,))

    def step(state, xs):
        D, d = xs
        result, state = _online_step(
            state, D, s, d,
            use_kernel=use_kernel, do_equalize=do_equalize,
            merge_aware=merge_aware, extra_slots=extra_slots,
            matcher=matcher, repair_rounds=repair_rounds,
            warm_start=warm_start, warm_prices=warm_prices,
            warm_slack=warm_slack,
        )
        return state, result

    final_state, results = jax.lax.scan(
        step, online_initial_state(n, s, cache_size), (Ds, deltas)
    )
    return results, final_state
