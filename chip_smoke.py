#!/usr/bin/env python3
"""Smoke test of the SPECTRA device path on one TPU, through its entry points.

    python chip_smoke.py

Needs a TPU: with any other JAX platform it exits non-zero before printing
a result. Runs in this one process and starts no children (a chip belongs
to one process at a time). Every phase builds its demand from seeds through
``repro.scenarios`` and checks the device result against the host SPECTRA
reference; any failed check raises, so the script exits non-zero.

Phases (one line each, plus one per scenario in phase a):

a. served path: ``ScheduleServer(mode="async", solver="spectra_jax")`` with
   validation on, two tenants, a few periods of ``moe`` (n=64),
   ``benchmark`` (n=100) and ``benchmark_large`` (n=256);
b. pod scale: one ``solve_many`` over ``pod_1024`` (n=1024);
c. the fused auction kernel against its jnp reference at n=256;
d. online path: ``run_scenario("moe", online=True)``, one ``lax.scan``.

``compile_s`` is the JAX trace + lower + backend-compile time of a phase's
first run and ``steady_s`` the wall time of its second run; both are
informational, not metrics. The last line of standard output is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

S, DELTA = 4, 0.01
# Device/host makespan gates, the ones tier-1 holds: DEVICE_QUALITY_TRIPWIRE
# (tests/test_scenarios.py) for n <= 100, and the pod-scale tie-break
# tripwire of tests/test_auction_fused.py at n=1024.
GATE_SMALL = 1.10
GATE_POD = 1.15
# benchmark_large (n=256) has no tier-1 gate. Tier-1 holds 1.01 at n=256 on
# a sum-of-permutations matrix, which this scenario does not meet: in one
# round the auction returns a matching that is ε-optimal but 3.4e-5 short of
# the host's exact optimum, and greedy REFINE turns that into a per-period
# makespan spread both ways. The same device algorithm on the CPU gave
# 0.870 to 1.194 over 12 periods (geomean 1.013). That is the tie-break gap
# of ROADMAP Reach A1, not a bring-up fault, so this gate is a tripwire for
# gross failure (a matcher that stalls gave 1.36 at n=100) above that spread.
GATE_N256 = 1.25
# permutations_large (n=512) is left out: it fails its host reference today
# (ROADMAP Reach A1), which is not a bring-up fault either.

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class SmokeFailure(AssertionError):
    """A phase's check failed."""


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


@dataclass(frozen=True)
class Sizes:
    """What each phase runs: scenario names, overrides and gates."""

    served: tuple[tuple[str, dict, float], ...]  # (scenario, overrides, gate)
    served_periods: int
    pod: tuple[str, dict, float]
    kernel_n: int
    online: tuple[str, dict]


FULL = Sizes(
    served=(
        ("moe", {}, GATE_SMALL),
        ("benchmark", {}, GATE_SMALL),
        ("benchmark_large", {}, GATE_N256),
    ),
    served_periods=4,
    pod=("pod_1024", {}, GATE_POD),
    kernel_n=256,
    online=("moe", {}),
)


@dataclass
class CompileMeter:
    """Sums JAX compile time and persistent-cache hits while entered."""

    compile_s: float = 0.0
    cache_hits: int = 0
    cache_misses: int = 0

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += duration

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def __enter__(self) -> "CompileMeter":
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)


def _timed(meter: CompileMeter, fn):
    """Run ``fn`` twice; returns both results, the compile time of the
    first run and the wall time of the second."""
    c0 = meter.compile_s
    first = fn()
    compile_s = meter.compile_s - c0
    t0 = time.perf_counter()
    second = fn()
    return first, second, compile_s, time.perf_counter() - t0


def _line(phase: str, **kv) -> None:
    body = " ".join(
        f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in kv.items()
    )
    print(f"phase={phase} {body}", flush=True)


def _host_makespan(D) -> float:
    from repro.api import Problem, SolveOptions, solve

    return solve(
        Problem(D, S, DELTA), solver="spectra",
        options=SolveOptions(validate=False, compute_lb=False),
    ).makespan


def _check_reports(reports, mats, host, gate: float, tag: str) -> list:
    """Device reports against the host reference; returns the
    device/host makespan ratio of each matrix."""
    ratios = []
    for rep, D, h in zip(reports, mats, host):
        check(rep.extras["use_kernel"] is True, f"{tag}: kernels were off")
        check(rep.validated, f"{tag}: schedule was not validated (Eq. 3)")
        dev = rep.extras["device_makespan"]
        if not rep.extras["eq_exhausted"]:
            check(
                abs(dev - rep.makespan) <= 1e-4 * rep.makespan,
                f"{tag}: device makespan {dev} != schedule's {rep.makespan}",
            )
        check(rep.makespan >= rep.lower_bound * (1 - 1e-4),
              f"{tag}: makespan {rep.makespan} below LB {rep.lower_bound}")
        ratios.append(rep.makespan / h)
    check(max(ratios) <= gate, f"{tag}: device/host makespan ratios "
                               f"{ratios} exceed the {gate} gate")
    return ratios


def _ratios(ratios: list) -> dict:
    return dict(ratio_max=max(ratios),
                ratios=",".join(f"{r:.6g}" for r in ratios))


def _lowers_to_mosaic(fn, *args, **kwargs) -> bool:
    return "tpu_custom_call" in fn.lower(*args, **kwargs).as_text()


def phase_served(sizes: Sizes, meter: CompileMeter, mosaic: bool) -> None:
    from repro.api import SolveOptions
    from repro.core.jaxopt.e2e import spectra_jax_e2e_many
    from repro.scenarios import make_trace
    from repro.serve.server import ScheduleServer

    options = SolveOptions(validate=True)
    for name, overrides, gate in sizes.served:
        mats = make_trace(
            name, periods=sizes.served_periods, **overrides
        ).demands
        n = mats.shape[-1]

        def serve():
            srv = ScheduleServer(S, DELTA, mode="async", solver="spectra_jax",
                                 options=options, max_batch=len(mats))
            tickets = [srv.submit(f"tenant{t % 2}", D)[0]
                       for t, D in enumerate(mats)]
            srv.drain()
            check(srv.mode == "async", f"{name}: server left async mode")
            res = [srv.results[t] for t in tickets]
            check(all(r.source == "device" for r in res),
                  f"{name}: a request was not solved on the device")
            return [r.report for r in res]

        first, reports, compile_s, steady_s = _timed(meter, serve)
        host = [_host_makespan(D) for D in mats]
        _check_reports(first, mats, host, gate, name)
        ratios = _check_reports(reports, mats, host, gate, name)
        ex = reports[0].extras
        if mosaic:
            check(_lowers_to_mosaic(
                spectra_jax_e2e_many, mats.astype(np.float32), S, DELTA,
                use_kernel=True, matcher=ex["matcher"],
            ), f"{name}: the served program holds no Mosaic kernel")
        _line("a_served", scenario=name, n=n, B=ex["batch_size"],
              tenants=2, matcher=ex["matcher"], use_kernel=ex["use_kernel"],
              mode="async", compile_s=compile_s, steady_s=steady_s,
              **_ratios(ratios), gate=gate)


def phase_pod(sizes: Sizes, meter: CompileMeter) -> None:
    from repro.api import SolveOptions, solve_many
    from repro.scenarios import make_trace

    name, overrides, gate = sizes.pod
    mats = make_trace(name, **overrides).demands

    def run():
        return solve_many(mats, S, DELTA, solver="spectra_jax",
                          options=SolveOptions(validate=True))

    first, reports, compile_s, steady_s = _timed(meter, run)
    host = [_host_makespan(D) for D in mats]
    _check_reports(first, mats, host, gate, name)
    ratios = _check_reports(reports, mats, host, gate, name)
    ex = reports[0].extras
    _line("b_pod", scenario=name, n=mats.shape[-1], B=len(mats),
          matcher=ex["matcher"], use_kernel=ex["use_kernel"],
          compile_s=compile_s, steady_s=steady_s, **_ratios(ratios),
          gate=gate)


def _bonus_weights(D: np.ndarray) -> np.ndarray:
    """First-round DECOMPOSE benefit matrix: demand plus coverage M-bonus."""
    S_ = D > 0
    rd, cd = S_.sum(1), S_.sum(0)
    k = max(rd.max(), cd.max())
    M = D.max(axis=1).sum() + 1.0
    bonus = M * ((rd == k)[:, None].astype(float) + (cd == k)[None, :])
    return (D + np.where(S_, bonus, 0.0)).astype(np.float32)


def phase_kernel(sizes: Sizes, meter: CompileMeter, mosaic: bool) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.jaxopt.matching import (
        _eps_schedule,
        default_max_iters,
        default_num_phases,
    )
    from repro.kernels.auction_fused import fused_auction
    from repro.scenarios import make_trace

    n = sizes.kernel_n
    rng = np.random.default_rng(n)
    cases = {
        "uniform": rng.random((n, n), dtype=np.float32),
        "decompose": _bonus_weights(
            make_trace("benchmark", n=n, periods=1).demands[0]
        ),
    }
    for case, W_np in cases.items():
        W = jnp.asarray(W_np)
        p0 = jnp.zeros((n,), jnp.float32)
        eps = _eps_schedule(W, default_num_phases(n))
        kw = dict(max_iters=default_max_iters(n))
        if mosaic:
            check(_lowers_to_mosaic(fused_auction, W, p0, eps,
                                    use_kernel=True, **kw),
                  f"kernel/{case}: fused_auction did not lower to Mosaic")

        def run(use_kernel):
            return lambda: jax.block_until_ready(
                fused_auction(W, p0, eps, use_kernel=use_kernel,
                              with_iters=True, **kw))

        (kr2c, _, kp, kit), _, compile_s, steady_s = _timed(meter, run(True))
        (rr2c, _, rp, rit), _, _, ref_steady_s = _timed(meter, run(False))
        kr2c, rr2c = np.asarray(kr2c), np.asarray(rr2c)
        for tag, r2c in (("kernel", kr2c), ("reference", rr2c)):
            check(np.array_equal(np.sort(r2c), np.arange(n)),
                  f"kernel/{case}: {tag} assignment is not a permutation")
        rows = np.arange(n)
        w_k = float(W_np[rows, kr2c].astype(np.float64).sum())
        w_r = float(W_np[rows, rr2c].astype(np.float64).sum())
        slack = n * float(eps[-1])
        check(abs(w_k - w_r) <= slack,
              f"kernel/{case}: weights {w_k} vs {w_r} differ by more than "
              f"n*eps_final={slack}")
        identical = bool(np.array_equal(kr2c, rr2c)
                         and np.array_equal(np.asarray(kp), np.asarray(rp))
                         and np.array_equal(np.asarray(kit), np.asarray(rit)))
        _line("c_kernel", case=case, n=n, B=1, matcher="auction_fused",
              use_kernel=True, compile_s=compile_s, steady_s=steady_s,
              ref_steady_s=ref_steady_s, bid_rounds=int(np.sum(kit)),
              weight_diff=abs(w_k - w_r),
              slack=slack, bit_identical=identical)


def phase_online(sizes: Sizes, meter: CompileMeter) -> None:
    from repro.api import SolveOptions
    from repro.scenarios import run_scenario

    name, overrides = sizes.online

    def run():
        return run_scenario(name, solver="spectra_jax", online=True,
                            options=SolveOptions(validate=True), **overrides)

    first, rep, compile_s, steady_s = _timed(meter, run)
    for r in (first, rep):
        check(all(x.extras["use_kernel"] is True for x in r.reports),
              f"online/{name}: kernels were off")
        bad = [p.period for p in r.online_periods
               if p.makespan > p.stateless_makespan * (1 + 1e-6) + 1e-9]
        check(not bad, f"online/{name}: periods {bad} break the "
                       "online <= stateless guarantee")
    ratio = max(p.makespan / p.stateless_makespan for p in rep.online_periods)
    _line("d_online", scenario=name, n=rep.spec.n, T=len(rep.online_periods),
          matcher=rep.reports[0].extras["matcher"], use_kernel=True,
          compile_s=compile_s, steady_s=steady_s,
          online_over_stateless_max=ratio, gate=1.0)


def run_phases(sizes: Sizes, *, mosaic: bool) -> CompileMeter:
    """All phases; ``mosaic`` also checks each program holds a TPU kernel."""
    from repro.kernels.auction_bid import ops as bid_ops
    from repro.kernels.auction_fused import ops as fused_ops

    # On a TPU no kernel may fall back to the Pallas interpreter.
    if mosaic:
        check(fused_ops.on_tpu() and bid_ops.on_tpu(),
              "kernel wrappers would run in interpret mode")
    with CompileMeter() as meter:
        phase_served(sizes, meter, mosaic)
        phase_pod(sizes, meter)
        phase_kernel(sizes, meter, mosaic)
        phase_online(sizes, meter)
    return meter


def main() -> int:
    if "REPRO_USE_KERNEL" in os.environ:
        print("chip_smoke: unset REPRO_USE_KERNEL; the kernels must turn on "
              "by backend detection", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels.backend import enable_compile_cache

    cache_dir = enable_compile_cache()
    print(f"device_kind={dev.device_kind} count={len(devices)} "
          f"jax={jax.__version__} compile_cache={cache_dir}", flush=True)
    t0 = time.perf_counter()
    meter = run_phases(FULL, mosaic=True)
    print(f"total_s={time.perf_counter() - t0:.6g} "
          f"compile_s={meter.compile_s:.6g} cache_hits={meter.cache_hits} "
          f"cache_misses={meter.cache_misses}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
