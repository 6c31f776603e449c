"""Observability: tracer invariants, makespan attribution, artifacts.

Fast lane (the CI ``obs-smoke`` job runs exactly this file under
``-m "not slow"``):

  * the span tracer round-trips through Chrome trace-event JSON, keeps
    spans well-nested (child ⊆ parent interval), and — disabled — returns
    a shared no-op singleton without allocating;
  * the attribution identity ``transmission + δ paid + idle ≡ s·makespan``
    holds with residual ≈ 0 on every registered scenario (stateless host),
    on the fused device path, and on the credit-aware online pass;
  * ``repro.serve.metrics`` re-exports ``repro.obs.metrics`` unchanged,
    warning counters categorize ``SolveReport.extras["warnings"]``, and
    the benchmark artifact writer round-trips its envelope.
"""

import json
import tracemalloc

import numpy as np
import pytest

from repro.api import Problem, solve
from repro.obs import (
    Counters,
    MakespanAttribution,
    ServeMetrics,
    Tracer,
    attribute_scenario,
    get_tracer,
    timeline_table,
    warning_category,
    warning_counts,
)
from repro.obs.trace import _NULL_SPAN
from repro.scenarios import list_scenarios, run_scenario

TINY = dict(n=8, periods=3)


@pytest.fixture(autouse=True)
def _clean_global_tracer():
    """No test leaves the module-level tracer enabled or populated."""
    tracer = get_tracer()
    yield
    tracer.disable()
    tracer.reset()


def _solve(seed: int = 0, delta: float = 0.01):
    n = 8
    rng = np.random.default_rng(seed)
    D = np.zeros((n, n))
    for _ in range(4):
        D[np.arange(n), rng.permutation(n)] += rng.uniform(0.5, 2.0, size=n)
    return solve(Problem(D, s=4, delta=delta))


# --------------------------------------------------------------- tracer


class TestTracer:
    def test_disabled_returns_shared_null_span(self):
        t = Tracer()
        assert t.span("a") is _NULL_SPAN
        assert t.span("b", {"k": 1}) is _NULL_SPAN
        assert t.events == []

    def test_disabled_span_is_allocation_free(self):
        t = Tracer()
        t.span("warmup")  # materialize the method/local caches
        tracemalloc.start()
        try:
            snap0 = tracemalloc.take_snapshot()
            for _ in range(100):
                with t.span("hot"):
                    pass
            snap1 = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        import repro.obs.trace as trace_mod

        flt = tracemalloc.Filter(True, trace_mod.__file__)
        stats = snap1.filter_traces([flt]).compare_to(
            snap0.filter_traces([flt]), "lineno"
        )
        grew = [s for s in stats if s.size_diff > 0]
        assert not grew, f"disabled spans allocated: {grew}"

    def test_nesting_and_parents(self):
        t = Tracer(enabled=True)
        with t.span("outer"):
            with t.span("mid"):
                with t.span("inner"):
                    pass
            with t.span("mid2"):
                pass
        spans = t.spans()
        by_name = {s.name: s for s in spans}
        assert set(by_name) == {"outer", "mid", "inner", "mid2"}
        assert by_name["outer"].parent is None
        assert t.events[by_name["mid"].parent] is by_name["outer"]
        assert t.events[by_name["inner"].parent] is by_name["mid"]
        assert t.events[by_name["mid2"].parent] is by_name["outer"]
        # Containment: every child's interval lies inside its parent's.
        for s in spans:
            if s.parent is not None:
                p = t.events[s.parent]
                assert p.start <= s.start and s.end <= p.end

    def test_exception_closes_children(self):
        t = Tracer(enabled=True)
        with pytest.raises(RuntimeError):
            with t.span("outer"):
                with t.span("inner"):
                    raise RuntimeError("boom")
        assert all(e.end is not None for e in t.events)
        assert t._stack() == []

    def test_chrome_round_trip(self, tmp_path):
        t = Tracer(enabled=True)
        with t.span("solve", {"n": 8}):
            pass
        t.counter("queue_depth", 3)
        path = t.save(tmp_path / "trace.json")
        doc = json.loads(path.read_text())
        events = doc["traceEvents"]
        assert {e["ph"] for e in events} == {"X", "C"}
        x = next(e for e in events if e["ph"] == "X")
        assert x["name"] == "solve" and x["args"] == {"n": 8}
        assert x["ts"] >= 0 and x["dur"] >= 0
        c = next(e for e in events if e["ph"] == "C")
        assert c["args"]["value"] == 3.0

    def test_set_attaches_args_at_exit(self):
        t = Tracer(enabled=True)
        with t.span("stage", {"in": 1}) as sp:
            sp.set(out=2)
        assert t.spans()[0].args == {"in": 1, "out": 2}

    def test_reset_and_reenable(self):
        t = Tracer(enabled=True)
        with t.span("a"):
            pass
        t.reset()
        assert t.events == []
        t.disable()
        assert t.span("b") is _NULL_SPAN


class TestPipelineWiring:
    def test_traced_run_scenario_emits_stage_spans(self):
        tracer = get_tracer()
        tracer.enable()
        run_scenario("gpt", **TINY)
        names = {s.name for s in tracer.spans()}
        assert {"solve_many", "decompose", "schedule", "equalize",
                "matcher", "install", "period"} <= names
        # Stage spans nest under the solve_many loop; matcher under decompose.
        by_name = {}
        for s in tracer.spans():
            by_name.setdefault(s.name, []).append(s)
        for s in by_name["matcher"]:
            chain = set()
            p = s.parent
            while p is not None:
                chain.add(tracer.events[p].name)
                p = tracer.events[p].parent
            assert "decompose" in chain

    def test_traced_online_run_emits_online_spans(self):
        tracer = get_tracer()
        tracer.enable()
        run_scenario("gpt", online=True, **TINY)
        names = {s.name for s in tracer.spans()}
        assert "online.period" in names


# ---------------------------------------------------------- attribution


class TestAttribution:
    def test_identity_on_single_solve(self):
        rep = _solve()
        table = timeline_table(rep)
        a = table.attribution
        a.check(1e-9)
        assert a.s == 4
        assert a.makespan == pytest.approx(rep.makespan)
        assert np.isfinite(a.lower_bound)  # picked up from the SolveReport
        assert a.transmission_share + a.delta_share + a.idle_share == pytest.approx(1.0)
        # Exact LB-gap decomposition.
        assert (
            a.gap_from_transmission + a.gap_from_delta + a.gap_from_idle
            == pytest.approx(a.lb_gap, abs=1e-12)
        )

    def test_rows_cover_horizon_exactly(self):
        rep = _solve(seed=3, delta=0.05)
        table = timeline_table(rep)
        for row in table.rows:
            assert row.serve_time + row.reconf_time + row.idle_time == pytest.approx(
                table.horizon, abs=1e-12
            )
            assert 0.0 <= row.utilization <= 1.0 + 1e-12
            # Intervals tile [0, horizon) in order without gaps.
            t = 0.0
            for iv in row.intervals:
                assert iv.start == pytest.approx(t, abs=1e-9)
                t = iv.end
            assert t == pytest.approx(table.horizon, abs=1e-9)

    def test_horizon_extension_grows_idle_only(self):
        rep = _solve()
        base = timeline_table(rep)
        longer = timeline_table(rep, horizon=base.horizon * 1.5)
        assert longer.attribution.transmission == pytest.approx(
            base.attribution.transmission
        )
        assert longer.attribution.delta_paid == pytest.approx(
            base.attribution.delta_paid
        )
        assert longer.attribution.idle > base.attribution.idle
        longer.attribution.check(1e-9)
        with pytest.raises(ValueError, match="shorter than the timeline"):
            timeline_table(rep, horizon=base.horizon * 0.5)

    @pytest.mark.parametrize("name", list_scenarios())
    def test_identity_on_every_registered_scenario(self, name):
        rep = run_scenario(name, **TINY)
        att = attribute_scenario(rep)
        att.check()
        agg = att.summary()
        assert agg["periods"] == len(rep.reports)
        assert agg["max_identity_residual"] <= att.tol
        assert 0.0 - att.tol <= agg["util_min"]
        assert agg["transmission_share"] + agg["delta_share"] + agg[
            "idle_share"
        ] == pytest.approx(1.0)

    def test_identity_online_pass(self):
        rep = run_scenario("gpt", online=True, **TINY)
        att = attribute_scenario(rep)
        att.check()
        assert len(att.online_tables) == len(rep.online_periods)
        agg = att.summary()
        assert agg["online_reuse_count"] == sum(
            p.reuse_count for p in rep.online_periods
        )
        assert agg["online_delta_avoided"] == pytest.approx(
            sum(p.delta_avoided for p in rep.online_periods)
        )
        # Reused switches start serving δ-free at t=0.
        reused_rows = [
            row for table in att.online_tables for row in table.rows if row.reused
        ]
        assert reused_rows, "gpt TINY online pass reuses configurations"
        for row in reused_rows:
            first = row.intervals[0]
            assert first.kind == "serve" and first.start == 0.0

    def test_identity_device_pass(self):
        rep = run_scenario("gpt", solver="spectra_jax", **TINY)
        att = attribute_scenario(rep)
        att.check()
        assert att.tol == 1e-4  # float32 device tolerance auto-resolved

    def test_per_round_spread(self):
        rep = _solve()
        rounds = timeline_table(rep).per_round()
        assert rounds and all(r["spread"] >= 0 for r in rounds)
        assert sum(r["alpha_total"] for r in rounds) == pytest.approx(
            timeline_table(rep).attribution.transmission
        )

    def test_render_ascii_shape(self):
        rep = _solve()
        art = timeline_table(rep).render_ascii(width=40)
        lines = art.splitlines()
        assert len(lines) == 5  # 4 switch strips + the axis line
        assert all("|" in ln for ln in lines)

    def test_check_raises_on_cooked_books(self):
        a = MakespanAttribution(
            s=4, makespan=1.0, transmission=3.0, delta_paid=0.5, idle=0.2
        )
        with pytest.raises(AssertionError, match="identity violated"):
            a.check(1e-9)


# -------------------------------------------------------------- metrics


class TestMetricsUnification:
    def test_serve_reexports_obs_metrics(self):
        import repro.obs.metrics as obs_metrics
        import repro.serve.metrics as serve_metrics

        assert serve_metrics.ServeMetrics is obs_metrics.ServeMetrics
        assert serve_metrics.ServeMetrics is ServeMetrics
        assert serve_metrics.LatencyHistogram is obs_metrics.LatencyHistogram
        assert serve_metrics.STAGES is obs_metrics.STAGES

    def test_warning_category(self):
        assert warning_category("matcher budget exhausted at round 3") == (
            "matcher_budget_exhausted"
        )
        assert warning_category("equalize: headroom exhausted") == (
            "equalize_headroom_exhausted"
        )
        assert warning_category("something else") == "other"

    def test_warning_counts_and_counters(self):
        rep = _solve()
        rep.extras["warnings"] = [
            "matcher budget exhausted",
            "equalize headroom exhausted",
            "equalize headroom exhausted",
        ]
        counters = warning_counts([rep])
        assert counters.get("matcher_budget_exhausted") == 1
        assert counters.get("equalize_headroom_exhausted") == 2
        assert counters.total == 3
        assert counters.export() == {
            "matcher_budget_exhausted": 1,
            "equalize_headroom_exhausted": 2,
        }

    def test_counters_basics(self):
        c = Counters()
        assert not c
        c.inc("a")
        c.inc("a", 2)
        assert c and c.get("a") == 3 and c.get("missing") == 0

    def test_scenario_summary_surfaces_warnings(self):
        rep = run_scenario("gpt", **TINY)
        rep.reports[0].extras.setdefault("warnings", []).append(
            "matcher budget exhausted"
        )
        row = rep.summary()
        assert row["warnings"] >= 1
        assert row["warning_counts"]["matcher_budget_exhausted"] >= 1


# ------------------------------------------------------------ artifacts


class TestBenchArtifacts:
    def test_round_trip(self, tmp_path):
        from benchmarks.artifact import SCHEMA, read_artifact, write_artifact

        path = write_artifact(
            "demo",
            {"rows": [{"n": 8, "us": 1.5}]},
            git_sha="deadbeef",
            timestamp="2026-01-01T00:00:00+00:00",
            workload="unit",
            out_dir=tmp_path,
        )
        assert path.name == "BENCH_demo.json"
        doc = read_artifact(path)
        assert doc["schema"] == SCHEMA
        assert doc["git_sha"] == "deadbeef"
        assert doc["workload"] == "unit"
        assert doc["metrics"]["rows"][0]["n"] == 8

    def test_read_rejects_unknown_schema(self, tmp_path):
        from benchmarks.artifact import read_artifact

        bad = tmp_path / "BENCH_bad.json"
        bad.write_text(json.dumps({"schema": "nope/v0"}))
        with pytest.raises(ValueError, match="unknown benchmark artifact schema"):
            read_artifact(bad)

    def test_git_sha_resolves_here(self):
        from benchmarks.artifact import git_sha

        sha = git_sha()
        assert sha is None or (len(sha) == 40 and set(sha) <= set("0123456789abcdef"))


# ------------------------------------------------------------ dashboard


class TestDashboard:
    def test_cli_smoke_writes_reports(self, tmp_path, capsys):
        from repro.obs.dashboard import main

        trace = tmp_path / "trace.json"
        html = tmp_path / "report.html"
        rc = main([
            "gpt", "--n", "8", "--periods", "2",
            "--trace", str(trace), "--html", str(html), "--width", "40",
        ])
        assert rc == 0
        out = capsys.readouterr().out
        assert "ocs0" in out and "horizon=" in out
        doc = json.loads(trace.read_text())
        assert doc["traceEvents"]
        text = html.read_text()
        assert "<html" in text and "obs-root" in text

    def test_flowsim_summary_attribution_keys(self):
        rep = run_scenario("gpt", flowsim=True, **TINY)
        fs = rep.flowsim_summary()
        assert 0.0 <= fs["delta_share"] <= 1.0
        assert 0.0 <= fs["idle_share"] <= 1.0
