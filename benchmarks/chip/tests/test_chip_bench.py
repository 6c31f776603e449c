"""CPU tests of the chip benchmark: its copies, its trace reduction, its
check and control, and one tiny cell driven end to end.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

Nothing here measures: a CPU run gives no device number, and the harness
reports none from one.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import check as chk  # noqa: E402
from chipbench import demand  # noqa: E402
from chipbench import harness as h  # noqa: E402
from chipbench import loop  # noqa: E402
from chipbench import plugins  # noqa: E402
from chipbench import reference as ref  # noqa: E402
from chipbench import trace as tr  # noqa: E402

RECORDED = BENCH / "tests" / "data"
SEED = 2**31 + 977  # more than 32 signed bits hold


# ------------------------------------------------------------ the copies
@pytest.mark.parametrize("scenario,config,n,knobs", [
    ("moe", "qwen_moe_64", 16, {"top_k": 6}),  # the scenario's own top-k
    ("benchmark", "perm_bench_100", 32, {}),
])
def test_traffic_copies_match_the_program(scenario, config, n, knobs):
    from repro.scenarios import make_trace

    conf = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    conf["n"] = n
    conf["demand"].update(knobs)
    want = make_trace(scenario, n=n, periods=3).demands
    for t in range(3):
        got = demand.generate(conf, np.random.default_rng(t))
        np.testing.assert_array_equal(got, want[t])


@pytest.mark.parametrize("family,kw", [
    ("moe", dict(n=24, skew=0.5, noise=0.01)),
    ("moe", dict(n=20, top_k=8)),
    ("benchmark", dict(n=20, m=8, num_big=2)),
    ("benchmark", dict(n=20, m=16, num_big=4)),
])
def test_generators_match_the_program_workloads(family, kw):
    from repro.traffic.workloads import benchmark_workload, moe_workload

    prog = {"moe": moe_workload, "benchmark": benchmark_workload}[family]
    for seed in (0, 5, SEED):
        got = demand.family(family)(np.random.default_rng(seed), **kw)
        want = prog(rng=np.random.default_rng(seed), **kw)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("family,n", [("moe", 12), ("benchmark", 16)])
def test_reference_matches_host_spectra_and_bound(family, n):
    from repro.api import Problem, SolveOptions, solve
    from repro.core.lower_bounds import lower_bound

    for seed in range(3):
        D = demand.family(family)(np.random.default_rng(seed), n=n)
        rep = solve(Problem(D, 4, 0.01), solver="spectra",
                    options=SolveOptions(validate=True))
        sw = ref.spectra(D, 4, 0.01)
        assert ref.makespan(sw, 0.01) == pytest.approx(rep.makespan, rel=1e-12)
        assert sum(len(w) for w in sw) == rep.num_configs
        assert ref.lower_bound(D, 4, 0.01) == pytest.approx(
            lower_bound(D, 4, 0.01), rel=1e-12)
        assert ref.malformed(sw, n, 4) is None
        assert (D - ref.coverage(sw, n)).max() <= 1e-12


def test_lower_bound_theorem2_lines():
    from repro.core.lower_bounds import lower_bound

    rng = np.random.default_rng(3)
    D = np.zeros((8, 8))
    for i in range(8):  # exactly s=4 nonzeros per row: Theorem 2 applies
        D[i, rng.choice(8, 4, replace=False)] = rng.random(4)
    assert ref.lower_bound(D, 4, 0.05) == pytest.approx(
        lower_bound(D, 4, 0.05), rel=1e-12)


# ------------------------------------------------------- trace reduction
def _xspace(ops, modules, host, dropped=None):
    """A text-proto trace: one TPU plane, one host plane."""
    def line(lid, name, events):
        evs = "".join(
            f"events {{ metadata_id: {mid} offset_ps: {s * 1000} "
            f"duration_ps: {(e - s) * 1000} }}\n" for s, e, mid in events)
        return f"lines {{ id: {lid} name: \"{name}\" timestamp_ns: 0\n{evs}}}\n"

    extra = [(*dropped, tr.DROPPED)] if dropped else []
    names = sorted({n for *_, n in ops + modules + host + extra})
    mid = {n: i + 1 for i, n in enumerate(names)}
    meta = "".join(f"event_metadata {{ key: {i} value {{ id: {i} name: "
                   f"\"{n}\" }} }}\n" for n, i in mid.items())
    dev = line(1, "XLA Ops", [(s, e, mid[n]) for s, e, n in ops])
    dev += line(2, "XLA Modules", [(s, e, mid[n]) for s, e, n in modules])
    dev += line(4, "XLA TraceMe", [(s, e, mid[n]) for s, e, n in extra])
    hst = line(3, "python", [(s, e, mid[n]) for s, e, n in host])
    return (f"planes {{ id: 1 name: \"/device:TPU:0\"\n{dev}{meta}}}\n"
            f"planes {{ id: 2 name: \"/host:CPU\"\n{hst}{meta}}}\n")


def test_trace_reduction_on_a_synthetic_trace(tmp_path):
    from jax.profiler import ProfileData

    # a while loop holding a fusion and a kernel call, then two more ops
    ops = [(100, 400, "%while.3 = while(...)"), (100, 200, "%fusion.1 = ..."),
           (250, 400, "%custom-call.7 = ..."), (600, 700, "%fusion.1 = ..."),
           (900, 1000, "%custom-call.7 = ...")]
    modules = [(100, 700, "jit_spectra_jax_e2e_many(12)"),
               (900, 1100, "jit_spectra_jax_e2e_many(12)")]
    host = [(400, 600, "bench.step"), (700, 900, "bench.submit")]
    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(
            _xspace(ops, modules, host)))
    trace = tr.load(str(tmp_path), ("bench.",))
    red = tr.reduce(trace, 0, 1200)
    # busy: [100,400] ∪ [600,700] ∪ [900,1000] = 500 ns of 1200
    assert red.busy_s == pytest.approx(500e-9)
    assert red.window_s == pytest.approx(1200e-9)
    assert red.idle_share == pytest.approx(1 - 500 / 1200)
    # self times: the while keeps what its body does not cover
    assert red.op_s == {"while.3": pytest.approx(50e-9),
                        "fusion.1": pytest.approx(200e-9),
                        "custom-call.7": pytest.approx(250e-9)}
    assert red.kernel_s("custom-call") == pytest.approx(250e-9)
    assert red.module("spectra_jax_e2e_many") == (pytest.approx(800e-9), 2)
    assert sorted(red.gaps, key=lambda g: (-g[1], g[0])) == [
        ("bench.step", pytest.approx(200e-9)),
        ("bench.submit", pytest.approx(200e-9)),
        ("host.other", pytest.approx(200e-9)),
        ("host.other", pytest.approx(100e-9)),
    ]
    # an execution that runs past the window's end does not count
    assert tr.reduce(trace, 0, 1050).module("spectra_jax_e2e_many")[1] == 1


def test_trace_reduction_on_a_recorded_chip_trace():
    """The first 0.8 s of a traced ``bench256.fresh`` run on a TPU v5 lite."""
    meta = json.loads((RECORDED / "bench256.json").read_text())
    trace = tr.load(str(RECORDED / "bench256.xplane.pb"))
    assert set(trace.devices) == {"/device:TPU:0"}
    red = tr.reduce(trace, meta["lo"], meta["hi"])
    assert 0 < red.busy_s <= red.window_s == pytest.approx(0.8)
    for key in ("busy_s", "window_s"):
        assert getattr(red, key) == pytest.approx(meta[key], rel=1e-12)
    fused = red.kernel_s("fused_auction_pallas")
    assert fused == pytest.approx(meta["fused_kernel_s"], rel=1e-12)
    assert 0 < fused <= red.busy_s
    # self times split the busy time with nothing counted twice
    assert sum(red.op_s.values()) == pytest.approx(red.busy_s, rel=1e-9)
    seconds, runs = red.module("spectra_jax_e2e_many")
    assert (seconds, runs) == (pytest.approx(meta["module_s"], rel=1e-12),
                               meta["module_runs"])
    assert runs >= 1 and seconds <= red.window_s


def test_trace_window_stops_where_events_were_dropped(tmp_path):
    from jax.profiler import ProfileData

    ops = [(0, 100, "fusion.1"), (150, 300, "fusion.2"), (400, 500, "fusion.3")]
    txt = _xspace(ops, [], [], dropped=(200, 900))
    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(txt))
    red = tr.reduce(tr.load(str(tmp_path)), 0, 1000)
    assert red.window_s == pytest.approx(200e-9)
    assert red.busy_s == pytest.approx(150e-9)


# --------------------------------------------------- the check, the loop
def _answer(D, s=4, delta=0.01, dtype=None):
    sw = ref.spectra(D, s, delta, dtype)
    return chk.Answer(0, 0, sw, ref.makespan(sw, delta))


def test_check_passes_the_reference_and_fails_its_faults():
    conf = {"s": 4, "delta": 0.01,
            "limits": {"cover_gap": 1e-5, "makespan_err": 1e-6,
                       "host_gap_q1": 1e-6}}
    D = demand.family("moe")(np.random.default_rng(1), n=12)
    good = _answer(D)
    r, lbs, _ = chk.check([good], lambda a: D, conf, unanswered=0,
                       window_compiles=0)
    assert chk.verdict(r, conf["limits"])[0], r
    assert lbs[0] <= good.makespan
    # an answer altered where it is produced: one weight shaved
    sw = tuple([(p, a) for p, a in w] for w in good.switches)
    p, a = sw[0][0]
    sw[0][0] = (p, a * 0.999)
    bad = chk.Answer(0, 0, sw, ref.makespan(sw, 0.01))
    r, _, _ = chk.check([bad], lambda a: D, conf, unanswered=0,
                     window_compiles=0)
    assert not chk.verdict(r, conf["limits"])[0]
    assert r["cover_gap"] > conf["limits"]["cover_gap"]
    # a reported makespan that is not the schedule's
    r, _, _ = chk.check([chk.Answer(0, 0, good.switches, good.makespan * 1.001)],
                     lambda a: D, conf, unanswered=0,
                     window_compiles=0)
    assert r["makespan_err"] > conf["limits"]["makespan_err"]
    # a configuration that is not a permutation
    sw = tuple(list(w) for w in good.switches)
    sw[1][0] = (np.zeros(12, dtype=np.int64), sw[1][0][1])
    r, _, _ = chk.check([chk.Answer(0, 0, sw, good.makespan)], lambda a: D,
                     conf, unanswered=0, window_compiles=0)
    assert r["malformed"] == 1


def test_bf16_control_fails_the_check():
    import ml_dtypes

    conf = json.loads((BENCH / "configs" / "qwen_moe_64.json").read_text())
    for seed in range(3):
        D = demand.generate(dict(conf, n=16), np.random.default_rng(seed))
        a = _answer(D, dtype=ml_dtypes.bfloat16)
        a.makespan = float(np.asarray(a.makespan, dtype=ml_dtypes.bfloat16))
        r, _, _ = chk.check([a], lambda _a: D, conf, unanswered=0,
                         window_compiles=0)
        ok, _ = chk.verdict(r, conf["limits"])
        assert not ok, r


def test_seeded_matrices_repeat_and_differ():
    conf = json.loads((BENCH / "configs" / "perm_bench_100.json").read_text())
    conf["n"] = 16
    a = demand.generate(conf, loop.matrix_rng(SEED, 3, 7))
    b = demand.generate(conf, loop.matrix_rng(SEED, 3, 7))
    c = demand.generate(conf, loop.matrix_rng(SEED, 3, 8))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


# ------------------------------------------------- one tiny cell, on CPU
@pytest.fixture
def tiny(monkeypatch):
    """``bench100.fresh`` at n=8 for a 2 s window, on the CPU."""
    monkeypatch.setattr(h, "enable_compile_cache", lambda jax: "off")

    def make(trace=0, cell="bench100.fresh"):
        c = h.load_cell(cell, trace)
        c.config = dict(c.config, n=8, trace_seconds=1)
        return c

    return make


def _run(cell, **kw):
    kw.setdefault("trace", False)
    return h.run_cell(cell, seed=SEED, seconds=2.0, t_process=time.perf_counter(),
                      require_tpu=False, log=lambda m: None, **kw)


def test_tiny_cell_end_to_end_on_cpu(tiny):
    out = _run(tiny())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 8
    assert set(out["metrics"]) == {"latency_p50_s", "makespan_over_lb",
                                   "setup_s"}
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"], name


def test_tiny_traced_cell_reports_no_device_metric_on_cpu(tiny):
    out = _run(tiny(trace=1), trace=True)
    assert out["correct"], out["checks"]
    # the serve layer's own span is there; nothing read from a device trace
    assert set(out["metrics"]) == {"serve.queue_wait_p50_s"}
    assert "busy_s" not in out["device"] and "breakdown" not in out


def test_tiny_cell_control_is_not_correct(tiny):
    out = _run(tiny(), control="bf16")
    assert not out["correct"]
    limits = tiny().config["limits"]
    assert out["checks"]["cover_gap"]["value"] > limits["cover_gap"]


def test_fault_answer_altered_where_produced(tiny, monkeypatch):
    """Each schedule's first weight shaved by 5e-5 on its way out of the
    device: inside the program's own 1e-4 validation, outside the check."""
    import repro.api.jax_backend as jb

    real = jb._HostBatch.__init__

    def shaved(self, res, deltas, **kw):
        real(self, res, deltas, **kw)
        self.alphas = self.alphas.copy()
        self.alphas[:, 0] = np.maximum(self.alphas[:, 0] - 5e-5, 0.0)

    monkeypatch.setattr(jb._HostBatch, "__init__", shaved)
    out = _run(tiny())
    assert not out["correct"]
    assert out["checks"]["cover_gap"]["value"] > out["checks"]["cover_gap"]["limit"]


def test_fault_half_the_batch_left_out(tiny, monkeypatch):
    import repro.api.jax_backend as jb

    real = jb.PendingBatch.collect

    def half(self):
        reports = real(self)
        return reports[: len(reports) // 2]

    monkeypatch.setattr(jb.PendingBatch, "collect", half)
    out = _run(tiny())
    assert not out["correct"]
    assert out["failed"] > 0 and out["checks"]["unanswered"]["value"] > 0


# ----------------------------------------------------- the entry point
def _cli(cwd, *args, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "REPRO_USE_KERNEL"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "moe64.fresh",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_entry_point_refuses_a_cpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_entry_point_refuses_a_forced_kernel_setting():
    p = _cli(ROOT, env_extra={"REPRO_USE_KERNEL": "1"})
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_entry_point_refuses_a_tree_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_json_names_a_file_for_everything():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for conf in bench["configs"]:
        c = json.loads((ROOT / conf["file"]).read_text())
        assert c["name"] == conf["name"]
        assert callable(plugins.load("demand", c["demand"]["family"]).generate)
        assert callable(plugins.load("servers", c["serve"]["server"]).make)
    for cell in bench["workloads"]:
        traffic = json.loads(
            (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
        driver = plugins.load("drivers", traffic["driver"])
        assert callable(driver.warm_up) and callable(driver.run)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(plugins.load("metrics", m["name"]).read), m["name"]


def test_a_new_file_is_found_by_name(tmp_path, monkeypatch):
    """A family, driver, server or metric that a later cell adds is one
    new file; the loader finds it without an edit elsewhere."""
    (tmp_path / "demand").mkdir()
    (tmp_path / "demand" / "ones.py").write_text(
        "import numpy as np\n"
        "def generate(rng, *, n):\n"
        "    return np.ones((n, n))\n")
    monkeypatch.setattr(plugins, "BENCH_DIR", tmp_path)
    monkeypatch.setattr(plugins, "_loaded", {})
    D = demand.generate({"n": 3, "demand": {"family": "ones"}},
                        np.random.default_rng(0))
    np.testing.assert_array_equal(D, np.ones((3, 3)))
    with pytest.raises(FileNotFoundError):
        plugins.load("drivers", "no_such_driver")
