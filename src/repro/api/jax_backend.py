"""JAX backend for the unified solver API: the whole pipeline on device.

DECOMPOSE (ε-scaling auction), SCHEDULE (device LPT), and EQUALIZE
(``lax.while_loop`` over the dense ``DeviceSchedule`` IR) are fused into one
jitted call — ``repro.core.jaxopt.spectra_jax_e2e`` — and ``solve_many``
drains a whole stack of demand matrices through its ``vmap`` in a single
device call. Reports come back with device-computed makespans and *lazy*
host schedules: the Python-object ``ParallelSchedule`` is only materialized
when something touches it (validation, simulation, inspection), so the hot
path never loops over instances on the host.

``SolveOptions.extra`` knobs: ``use_kernel`` (Pallas kernels; unset →
backend detection via ``kernels.backend.resolve_use_kernel``: on by default
on TPU, off elsewhere unless ``REPRO_USE_KERNEL`` forces interpret mode),
``equalize`` (default True), ``merge_aware`` (SPECTRA++ merge-aware device
EQUALIZE), ``extra_slots`` (EQUALIZE split headroom, default 64),
``matcher`` (device MWM solver name from ``core.jaxopt.matching.MATCHERS``;
unset → autotuned per shape bucket by ``matching.default_matcher``:
``auction`` at n ≤ 32, ``auction_fr`` to 128, ``auction_fused`` above),
``repair_rounds`` (post-REFINE device local-search sweeps, default 0 =
paper-faithful Alg. 1+2).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core.decompose import Decomposition
from ..core.equalize import equalize
from ..core.jaxopt.e2e import E2EResult, spectra_jax_e2e, spectra_jax_e2e_many
from ..core.schedule_ir import DeviceSchedule, LazySchedule, ir_to_schedule
from ..kernels.backend import resolve_use_kernel
from ..obs.trace import get_tracer
from .problem import Problem, SolveOptions, SolveReport, finish_report


def _e2e_kwargs(options: SolveOptions, n: int) -> dict:
    from ..core.jaxopt.matching import default_matcher

    return dict(
        use_kernel=resolve_use_kernel(options.extra.get("use_kernel")),
        do_equalize=bool(options.extra.get("equalize", True)),
        merge_aware=bool(options.extra.get("merge_aware", False)),
        extra_slots=int(options.extra.get("extra_slots", 64)),
        # Autotuned per shape bucket unless the caller pins one: every
        # instance in a fused dispatch shares n, so the bucket IS the
        # autotuning granularity.
        matcher=str(options.extra.get("matcher") or default_matcher(n)),
        repair_rounds=int(options.extra.get("repair_rounds", 0)),
    )


class _LazyDecomposition(Decomposition):
    """A ``Decomposition`` whose Python lists build on first access.

    Keeps the batched hot path free of per-round list construction: the
    report carries the per-instance arrays (one vectorized copy), and the
    O(k) object materialization happens only if a consumer actually reads
    ``perms``/``alphas``.
    """

    def __init__(self, perms_arr: np.ndarray, alphas_arr: np.ndarray):
        self._perms_arr = perms_arr
        self._alphas_arr = alphas_arr
        self._inner: Decomposition | None = None

    def _force(self) -> Decomposition:
        if self._inner is None:
            self._inner = Decomposition(
                perms=[p.astype(np.int64) for p in self._perms_arr],
                alphas=[float(a) for a in self._alphas_arr],
            )
        return self._inner

    @property
    def perms(self):  # type: ignore[override]
        return self._force().perms

    @property
    def alphas(self):  # type: ignore[override]
        return self._force().alphas


class _HostBatch:
    """One device→host transfer for a whole fused batch, shared by B reports."""

    def __init__(
        self,
        res: E2EResult,
        deltas: np.ndarray,
        *,
        merge_aware: bool = False,
        matcher: str = "auction",
        repair_rounds: int = 0,
        use_kernel: bool = False,
        **_ignored,
    ):
        sched = res.schedule
        self.merge_aware = merge_aware
        self.matcher = matcher
        self.repair_rounds = repair_rounds
        self.use_kernel = use_kernel
        self.perms = np.asarray(sched.perms)
        self.alphas = np.asarray(sched.alphas, dtype=np.float64)
        self.switch = np.asarray(sched.switch)
        self.makespans = np.asarray(res.makespan, dtype=np.float64)
        self.lpt_makespans = np.asarray(res.lpt_makespan, dtype=np.float64)
        self.dec_perms = np.asarray(res.dec.perms)
        self.dec_alphas = np.asarray(res.dec.alphas, dtype=np.float64)
        self.k = np.asarray(res.dec.k)
        self.converged = np.asarray(res.dec.converged)
        self.bid_rounds = np.asarray(res.dec.bid_rounds)
        self.bid_rounds_run = np.asarray(res.dec.bid_rounds_run)
        self.eq_exhausted = np.asarray(res.eq_exhausted)
        self.lbs = np.asarray(res.lb, dtype=np.float64)
        # Per-instance δ (trace-aware sweeps batch mixed δs in one dispatch).
        B = self.makespans.shape[0]
        self.deltas = np.broadcast_to(
            np.asarray(deltas, dtype=np.float64), (B,)
        )

    def decomposition(self, b: int) -> Decomposition:
        """Host Decomposition of instance b (pre-EQUALIZE weights), as the
        pre-fusion backend attached to every report — lazily materialized,
        with per-instance array copies so it doesn't pin the batch."""
        k = int(self.k[b])
        return _LazyDecomposition(
            self.dec_perms[b][:k].copy(), self.dec_alphas[b][:k].copy()
        )

    def schedule_thunk(self, b: int, s: int):
        # Copy the per-instance slices so a report that outlives the flush
        # pins O(R·n) of its own data, not the whole batch's arrays.
        perms = self.perms[b].copy()
        alphas = self.alphas[b].copy()
        switch = self.switch[b].copy()
        delta = float(self.deltas[b])
        exhausted = bool(self.eq_exhausted[b])
        merge_aware = self.merge_aware

        def build():
            ds = DeviceSchedule(
                perms=perms, alphas=alphas, switch=switch, delta=delta
            )
            sched = ir_to_schedule(ds, s)
            if exhausted:
                # Device EQUALIZE ran out of split headroom; host EQUALIZE
                # picks up exactly where it stopped, restoring host parity.
                sched = equalize(sched, merge_aware=merge_aware)
            return sched

        return build

    def report(
        self,
        b: int,
        problem: Problem,
        options: SolveOptions,
        runtime_s: float,
        *,
        extras: dict | None = None,
        device_lb: bool = True,
    ) -> SolveReport:
        lazy = LazySchedule(self.schedule_thunk(b, problem.s), float(self.deltas[b]))
        device_makespan = float(self.makespans[b])
        exhausted = bool(self.eq_exhausted[b])
        converged = bool(self.converged[b])
        # Warning-bearing surface: device-side degradations that would
        # otherwise hide in telemetry booleans. Consumers can gate on
        # ``extras["warnings"]`` without knowing each flag.
        warnings: list[str] = []
        if not converged:
            warnings.append(
                f"device matcher {self.matcher!r} exhausted its iteration "
                "budget (JaxDecomposition.converged=False); the matching — "
                "and the decomposition built on it — may be suboptimal"
            )
        if exhausted:
            warnings.append(
                "device EQUALIZE ran out of split headroom (raise "
                "options.extra['extra_slots']); host EQUALIZE finished the "
                "schedule at materialization"
            )
        all_extras = {
            "k": int(self.k[b]),
            "converged": converged,
            # This instance's bidding rounds, and the rounds the batched
            # matcher loops ran while it was matching (see JaxDecomposition).
            "bid_rounds": int(self.bid_rounds[b]),
            "bid_rounds_run": int(self.bid_rounds_run[b]),
            "matcher": self.matcher,
            "use_kernel": self.use_kernel,
            "repair_rounds": self.repair_rounds,
            "device_makespan": device_makespan,
            "device_lpt_makespan": float(self.lpt_makespans[b]),
            # True when device EQUALIZE ran out of split headroom before the
            # ≤δ spread (raise options.extra["extra_slots"]); the schedule
            # thunk finishes with host EQUALIZE, so metrics come from it.
            "eq_exhausted": exhausted,
            "warnings": warnings,
        }
        all_extras.update(extras or {})
        return finish_report(
            solver="spectra_jax",
            backend="jax",
            schedule=lazy,
            problem=problem,
            options=options,
            runtime_s=runtime_s,
            decomposition=self.decomposition(b),
            # Exhausted instances materialize eagerly so makespan/configs
            # reflect the host-finished schedule, not the truncated one.
            makespan=None if exhausted else device_makespan,
            num_configs=(
                None if exhausted else int((self.switch[b] >= 0).sum())
            ),
            # Batched path: §IV bound computed inside the fused device call
            # (float32) — no per-instance host loop. Single-instance solves
            # keep the exact float64 host bound (device_lb=False): one cheap
            # O(n²) pass with nothing to amortize.
            lower_bound=float(self.lbs[b]) if device_lb else None,
            extras=all_extras,
        )


def solve_spectra_jax(problem: Problem, options: SolveOptions) -> SolveReport:
    """Registry entry: one instance, full DECOMPOSE→SCHEDULE→EQUALIZE on device."""
    D = jnp.asarray(np.asarray(problem.D), jnp.float32)
    kwargs = _e2e_kwargs(options, problem.n)
    t0 = time.perf_counter()
    res = spectra_jax_e2e(D, problem.s, jnp.float32(problem.delta), **kwargs)
    jax.block_until_ready(res.makespan)
    runtime_s = time.perf_counter() - t0
    batch = _HostBatch(
        jax.tree_util.tree_map(lambda x: x[None], res),
        np.array([problem.delta]),
        **kwargs,
    )
    return batch.report(0, problem, options, runtime_s, device_lb=False)


class PendingBatch:
    """A dispatched-but-uncollected fused batch: the async serving handle.

    ``dispatch_many_jax`` returns immediately after enqueueing the fused
    device call — JAX dispatches asynchronously, so the solve runs on the
    XLA worker threads while the host does other work (e.g. installing the
    *previous* period's schedules — the double-buffered serving loop in
    ``repro.serve.server``). ``collect()`` performs the only
    synchronization: it waits for the result tree once, then reads every
    buffer, so the wait adds no barrier the reads would not make.
    ``timings`` then holds ``collect_wait_s`` (waiting for the device) and
    ``collect_host_s`` (transfers, materialization, validation, reports).
    """

    def __init__(self, res: E2EResult, mats, s, deltas, options, kwargs, t0):
        self._res = res
        self._mats = mats
        self._s = s
        self._deltas = deltas
        self._options = options
        self._kwargs = kwargs
        self._t0 = t0
        self._reports: list[SolveReport] | None = None
        self.timings: dict[str, float] = {}

    def __len__(self) -> int:
        return int(self._mats.shape[0])

    @property
    def ready(self) -> bool:
        """Non-blocking readiness probe of the device computation."""
        try:
            return bool(self._res.makespan.is_ready())
        except AttributeError:  # non-jax array (already concrete)
            return True

    def collect(self) -> list[SolveReport]:
        """Wait for the device results and build the per-ticket reports.

        Idempotent — repeated calls return the same report list. Runtime
        accounting spans dispatch → collection (the wall-clock the device
        work occupied, whether or not the host overlapped it)."""
        if self._reports is None:
            tracer = get_tracer()
            with tracer.span(
                "jax.collect",
                {"B": len(self)} if tracer.enabled else None,
            ):
                t_wait = time.perf_counter()
                with tracer.span("jax.collect.wait"):
                    jax.block_until_ready(self._res)
                t_host = time.perf_counter()
                with tracer.span("jax.collect.host"):
                    batch = _HostBatch(self._res, self._deltas, **self._kwargs)
                    device_s = time.perf_counter() - self._t0
                    B = len(self)
                    self._reports = [
                        batch.report(
                            b,
                            Problem(
                                self._mats[b], self._s, float(self._deltas[b])
                            ),
                            self._options,
                            device_s / B,
                            extras={
                                "batched": True, "batch_size": B, "fused": True
                            },
                        )
                        for b in range(B)
                    ]
            self.timings = {
                "collect_wait_s": t_host - t_wait,
                "collect_host_s": time.perf_counter() - t_host,
            }
        return self._reports


def program_text(B: int, n: int, s: int, options: SolveOptions) -> str:
    """The optimized HLO of the fused program ``dispatch_many_jax`` runs
    for a batch of ``B`` (n, n) matrices on ``s`` switches: the same
    function, options and input shapes, so its instruction names are those
    of a device trace of that batch (``repro.obs.device.op_scopes`` maps
    them to stages). With the persistent compile cache on, the compile is
    a cache load."""
    return spectra_jax_e2e_many.lower(
        jax.ShapeDtypeStruct((B, n, n), jnp.float32),
        s,
        jax.ShapeDtypeStruct((B,), jnp.float32),
        **_e2e_kwargs(options, n),
    ).compile().as_text()


def dispatch_many_jax(
    Ds: np.ndarray,
    s: int,
    delta,
    options: SolveOptions,
) -> PendingBatch:
    """Enqueue one fused batched solve and return without waiting.

    The returned ``PendingBatch`` owns the in-flight device arrays;
    ``collect()`` synchronizes. See ``solve_many_jax`` for the batching
    semantics — this is the same dispatch with the barrier split off."""
    # Only the device input is float32; reports validate against the
    # caller's matrices, exactly like the single-instance path.
    mats = np.asarray(Ds, dtype=np.float64)
    B = mats.shape[0]
    deltas = np.broadcast_to(np.asarray(delta, dtype=np.float64), (B,))
    kwargs = _e2e_kwargs(options, int(mats.shape[-1]))
    tracer = get_tracer()
    t0 = time.perf_counter()
    with tracer.span(
        "jax.dispatch",
        {"B": B, "n": int(mats.shape[-1]), "s": int(s)}
        if tracer.enabled
        else None,
    ):
        res = spectra_jax_e2e_many(
            mats.astype(np.float32), s, deltas.astype(np.float32), **kwargs
        )
        if tracer.enabled and tracer.device_sync:
            # Opt-in: land device time inside the span that launched it
            # (serializes the async pipeline — tracing-only behavior).
            jax.block_until_ready(res.makespan)
    return PendingBatch(res, mats, s, deltas, options, kwargs, t0)


def solve_many_jax(
    Ds: np.ndarray,
    s: int,
    delta,
    options: SolveOptions,
) -> list[SolveReport]:
    """Batched path for ``solve_many``: DECOMPOSE, SCHEDULE, *and* EQUALIZE
    for the whole stack in one vmapped device call; per-instance host
    schedules materialize lazily (on validation/access), never eagerly.
    §IV lower bounds come from the same fused call (float32, parity ≤1e-7
    rel) instead of a per-instance host loop. ``delta`` is a scalar or a
    per-instance (B,) vector (trace-aware δ sweeps) — the fused call vmaps
    over it either way. Synchronous dispatch + collect; async callers use
    ``dispatch_many_jax`` and collect when they need the results."""
    return dispatch_many_jax(Ds, s, delta, options).collect()
