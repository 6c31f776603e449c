"""CPU tests of the per-layer metrics read from the program's own stage
scopes, counters and spans, on hand-built runs.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""

from __future__ import annotations

import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import harness as h  # noqa: E402
from chipbench import plugins  # noqa: E402
from chipbench import scopes  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.loop import Request  # noqa: E402

CONFIG = {"n": 8, "s": 4, "serve": {"options": {"validate": True}}}
SEED = 2**31 + 977


def _metric(name):
    return plugins.load("metrics", name).read


def _answer(i, flight, *, rounds=None, run=None, host_s=None, batch=2):
    extras = {"batch_size": batch}
    if rounds is not None:
        extras.update(bid_rounds=rounds, bid_rounds_run=run)
    timings = {"queue_wait_s": 0.0}
    if host_s is not None:
        timings["collect_host_s"] = host_s
    result = SimpleNamespace(report=SimpleNamespace(extras=extras),
                             flight=flight, timings=timings)
    return Request(i, 0, t_submit=float(i), ticket=i, t_done=float(i) + 1,
                   result=result)


def _run(requests, reduction=None):
    cell = h.Cell("cell", {}, CONFIG, {}, [])
    return h.Run(cell, 1.0, 0.0, 0.0, requests, reduction=reduction)


def _reduction(op_s, busy_s):
    return tr.Reduction(window_s=1.0, busy_s=busy_s, op_s=op_s, op_text={},
                        module_s={}, module_runs={}, gaps=[])


@pytest.fixture
def fresh_map():
    scopes._scope_map.cache_clear()
    yield
    scopes._scope_map.cache_clear()


# ------------------------------------------------------------ chipbench.scopes
def test_scope_seconds_sums_self_time_by_stage(fresh_map):
    from repro.api import SolveOptions
    from repro.api.jax_backend import program_text
    from repro.obs.device import op_scopes

    scope_of = op_scopes(program_text(2, 8, 4, SolveOptions(validate=True)))
    match = next(i for i, s in scope_of.items() if s == "spectra.decompose.match")
    peel = next(i for i, s in scope_of.items() if s == "spectra.decompose.peel")
    red = _reduction({match: 0.5, peel: 0.25, "not-an-instruction": 0.125},
                     busy_s=1.0)
    run = _run([_answer(0, 0), _answer(1, 0)], red)
    assert scopes.scope_seconds(run) == {
        "spectra.decompose.match": 0.5, "spectra.decompose.peel": 0.25,
        scopes.UNSCOPED: 0.125}
    assert _metric("matcher.busy_share")(run) == 0.5


def test_scope_seconds_is_none_without_trace_answers_or_map(
        fresh_map, monkeypatch):
    red = _reduction({"fusion.1": 1.0}, busy_s=1.0)
    assert scopes.scope_seconds(_run([_answer(0, 0)])) is None
    assert scopes.scope_seconds(_run([], red)) is None
    # a program older than its stage scopes has no program_text
    import repro.api.jax_backend as jb

    monkeypatch.delattr(jb, "program_text")
    assert scopes.scope_seconds(_run([_answer(0, 0)], red)) is None
    assert _metric("matcher.busy_share")(_run([_answer(0, 0)], red)) is None


def test_busy_share_is_none_without_busy_time():
    read = _metric("matcher.busy_share")
    assert read(_run([_answer(0, 0)])) is None
    assert read(_run([_answer(0, 0)], _reduction({}, busy_s=0.0))) is None


# --------------------------------------------------------------- counters
def test_rounds_p50_is_the_median_over_answers():
    read = _metric("matcher.rounds_p50")
    answers = [_answer(i, i // 2, rounds=r, run=30)
               for i, r in enumerate((10, 30, 20, 5))]
    assert read(_run(answers)) == 15
    assert read(_run([])) is None
    assert read(_run([_answer(0, 0)])) is None  # no counter in the program


def test_lane_useful_share_is_the_median_over_flights():
    read = _metric("matcher.lane_useful_share")
    answers = [
        _answer(0, 0, rounds=10, run=20), _answer(1, 0, rounds=20, run=20),
        _answer(2, 1, rounds=5, run=5), _answer(3, 1, rounds=5, run=5),
        _answer(4, 2, rounds=1, run=4), _answer(5, 2, rounds=3, run=3),
    ]
    # flights: 30/40, 10/10, 4/8
    assert read(_run(answers)) == 0.75
    assert read(_run([])) is None
    assert read(_run([_answer(0, 0)])) is None
    no_flight = _answer(0, 0, rounds=1, run=1)
    del no_flight.result.flight
    assert read(_run([no_flight])) is None


# ------------------------------------------------------------------ spans
def test_collect_host_p50_counts_each_flight_once():
    read = _metric("api.collect_host_p50_s")
    answers = ([_answer(i, 0, host_s=0.5) for i in range(3)]
               + [_answer(3, 1, host_s=0.25), _answer(4, 2, host_s=0.125)])
    assert read(_run(answers)) == 0.25
    assert read(_run([])) is None
    assert read(_run([_answer(0, 0)])) is None


def test_tiny_traced_cell_reports_the_program_metrics(monkeypatch):
    """On the CPU a traced run has no device trace, so the matcher's busy
    share is left out; the counters and the collect span are there."""
    monkeypatch.setattr(h, "enable_compile_cache", lambda jax: "off")
    cell = h.load_cell("bench100.fresh", 1)
    cell.config = dict(cell.config, n=8, trace_seconds=1)
    out = h.run_cell(cell, seed=SEED, seconds=2.0, trace=True,
                     t_process=time.perf_counter(), require_tpu=False,
                     log=lambda m: None)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert "matcher.busy_share" not in m
    assert m["matcher.rounds_p50"]["value"] > 0
    assert 0 < m["matcher.lane_useful_share"]["value"] <= 1
    assert m["api.collect_host_p50_s"]["value"] > 0
