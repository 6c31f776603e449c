"""One run of one cell: set-up, the measured window, the check, one line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in the file that entry names, its
traffic mix in ``traffic/<name>.json``, and, under the benchmark's
directory, the configuration's demand family in ``demand/<family>.py``
and server in ``servers/<server>.py``, the mix's driver in
``drivers/<driver>.py``, and each metric in ``metrics/<name>.py`` (a
``read(run)`` that returns a number, or None when it finds nothing to
read). The last line of standard output is the result object; the last
lines of standard error are the numbers compared, each beside its limit.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

from . import check as chk
from . import plugins
from . import trace as tr
from .compile_meter import CompileMeter
from .demand import generate
from .loop import matrix_rng

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]

# Host spans that label the device's idle gaps: the harness's own, and
# the program's serve / api spans.
HOST_SPANS = ("bench.",)
PROGRAM_SPANS = ("serve.", "jax.")
ANCHOR = "bench.trace_start"  # puts the host clock on the trace's clock


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    spec: dict
    config: dict
    traffic: dict
    metrics: list[dict]


@dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    seconds: float
    setup_s: float
    t_start: float
    requests: list  # loop.Request, every request sent in the window
    lbs: dict = field(default_factory=dict)  # (tenant, period) -> bound
    reduction: tr.Reduction | None = None

    @property
    def t_end(self) -> float:
        return self.t_start + self.seconds

    @property
    def answered(self) -> list:
        return [r for r in self.requests if r.t_done is not None]


def load_cell(name: str, trace: int) -> Cell:
    """Cell ``name`` of ``BENCHMARK.json``, with the metrics a run with
    ``--trace <trace>`` reports for it."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = next((w for w in bench["workloads"] if w["name"] == name), None)
    if spec is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == spec["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{spec['traffic']}.json").read_text())
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in bench[kind]
               if name in m.get("workloads", [name])]
    return Cell(name, spec, config, traffic, metrics)


def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform!r} device(s)")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(jax) -> int | None:
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def enable_compile_cache(jax) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    the fixed ``<checkout>/.jax_cache``. Every program is kept, however
    quick its compile, so a second run compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def make_server(cell: Cell, control: str | None):
    c = cell.config
    if control == "bf16":
        import ml_dtypes

        return chk.ReferenceServer(int(c["s"]), float(c["delta"]),
                                   int(c["serve"]["max_batch"]),
                                   ml_dtypes.bfloat16)
    return plugins.load("servers", c["serve"]["server"]).make(c)


class Profiler:
    """A profiler trace of the window's first ``seconds``, stopped by a
    timer (a ``step`` may block for a whole batch), with the program's
    serve / api spans moved onto the trace's clock."""

    def __init__(self, jax, seconds: float, log=print):
        self.jax = jax
        self.seconds = seconds
        self.log = log
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        self.lock = threading.Lock()
        self.on = False

    def start(self) -> None:
        from repro.obs.trace import get_tracer

        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.tracer = get_tracer()
        self.tracer.reset()
        self.tracer.enable(device_sync=False)
        with self.jax.profiler.TraceAnnotation(ANCHOR):
            self.start_pc = time.perf_counter()
        self.stop_pc = self.start_pc
        self.on = True
        self.timer = threading.Timer(self.seconds, self.stop)
        self.timer.start()

    def stop(self) -> None:
        with self.lock:
            if not self.on:
                return
            self.on = False
            self.stop_pc = time.perf_counter()
            self.tracer.disable()
            self.jax.profiler.stop_trace()
            self.log(f"trace_stop_s={time.perf_counter() - self.stop_pc:.6f}")

    def finish(self) -> None:
        self.timer.cancel()
        self.timer.join()
        self.stop()

    def reduce(self) -> tr.Reduction | None:
        """The window's reduction; None when the trace holds no TPU."""
        t0 = time.perf_counter()
        trace = tr.load(self.dir, HOST_SPANS)
        self.log(f"trace_load_s={time.perf_counter() - t0:.6f} ops="
                 f"{sum(len(d.starts) for d in trace.devices.values())}")
        if not trace.devices:
            return None
        anchor = [h for h in trace.host_spans if h[2] == ANCHOR]
        if not anchor:
            raise RuntimeError(f"the trace lost the {ANCHOR} annotation")
        lo = anchor[0][0]
        offset = lo - round(self.start_pc * 1e9)
        hi = offset + round(self.stop_pc * 1e9)
        trace.host_spans = [h for h in trace.host_spans if h[2] != ANCHOR]
        trace.host_spans += [
            (round(e.start * 1e9) + offset, round(e.end * 1e9) + offset, e.name)
            for e in self.tracer.spans() if e.name.startswith(PROGRAM_SPANS)
        ]
        self.tracer.reset()
        return tr.reduce(trace, lo, hi)

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_process: float, control: str | None = None,
             require_tpu: bool = True, log=print) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax

    device = device_info(jax, int(cell.spec["chips"]), require_tpu)
    cache_dir = enable_compile_cache(jax)
    log(f"cell={cell.name} seed={seed} seconds={seconds} trace={int(trace)} "
        f"device={device['kind']} count={device['count']} "
        f"jax={jax.__version__} compile_cache={cache_dir}")
    demand_of = _demand_of(cell.config, seed)
    driver = plugins.load("drivers", cell.traffic["driver"])
    with CompileMeter() as meter:
        server = make_server(cell, control)
        driver.warm_up(server, cell.config, seed)
        setup_compile = meter.snapshot()
        profiler = None
        annotate = (jax.profiler.TraceAnnotation if trace
                    else lambda _name: contextlib.nullcontext())
        if trace:
            profiler = Profiler(
                jax, min(seconds, float(cell.config["trace_seconds"])), log)
            profiler.start()
        t_start = time.perf_counter()
        setup_s = t_start - t_process
        try:
            requests = driver.run(server, demand_of, cell.traffic, t_start,
                                  seconds, annotate)
        finally:
            if profiler is not None:
                profiler.finish()
        log(f"drive_s={time.perf_counter() - t_start:.6f}")
        window_compile = meter.snapshot()
    peak = memory_peak_bytes(jax)
    del server
    gc.collect()
    run = Run(cell, seconds, setup_s, t_start, requests)
    if profiler is not None:
        try:
            run.reduction = profiler.reduce()
        finally:
            profiler.close()
    log(f"setup_s={setup_s:.6f} compile_s={setup_compile[0]:.6f} "
        f"backend_compiles={setup_compile[1]} cache_hits={setup_compile[2]} "
        f"cache_misses={setup_compile[3]}")
    in_window = [b - a for a, b in zip(setup_compile, window_compile)]
    window_compiles = in_window[1] + in_window[2] + in_window[3]

    # The check, once the window has closed and the program's state is freed.
    t_check = time.perf_counter()
    answered = run.answered
    answers = [chk.answer_of(r) for r in answered]
    readings, lbs, notes = chk.check(
        answers, lambda a: demand_of(a.tenant, a.period), cell.config,
        unanswered=len(requests) - len(answered),
        window_compiles=window_compiles)
    run.lbs = {(a.tenant, a.period): lb for a, lb in zip(answers, lbs)}
    correct, checks = chk.verdict(readings, cell.config["limits"])
    log(f"check_s={time.perf_counter() - t_check:.6f} "
        f"answers={len(answers)} sent={len(requests)} "
        + " ".join(f"{k}={v!r}" for k, v in notes.items()))

    metrics = {}
    for m in cell.metrics:
        value = plugins.load("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device["memory_peak_bytes"] = peak
    out: dict[str, Any] = {
        "correct": bool(correct), "attempted": len(requests),
        "failed": len(requests) - len(answered), "metrics": metrics,
        "device": device,
    }
    if run.reduction is not None:
        red = run.reduction
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {"device_ops": [list(o) for o in ops],
                            "idle_gaps": [list(g) for g in red.gaps]}
    for name, c in checks.items():
        log(f"check {name}={c['value']!r} limit={c['limit']!r}")
    out["checks"] = checks
    return out


def _demand_of(config: dict, seed: int):
    """Request ``(tenant, period)``'s matrix; the same one when asked again."""

    def demand(tenant: int, period: int):
        return generate(config, matrix_rng(seed, tenant, period))

    return demand


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", choices=("bf16",), default=None,
                   help="put the bfloat16 host reference in the program's "
                        "place (the control of the check; no chip needed)")
    return p.parse_args(argv)


def main(argv, t_process: float) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    if "REPRO_USE_KERNEL" in os.environ:
        print("chipbench: unset REPRO_USE_KERNEL; the kernels turn on by "
              "backend detection", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    cell = load_cell(args.workload, args.trace)

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    try:
        out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), t_process=t_process,
                       control=args.control,
                       require_tpu=args.control is None, log=log)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0
