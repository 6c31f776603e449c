"""Observability: span tracing, time-expanded switch tables, shared metrics.

Four pillars, one package:

* ``trace`` — a lightweight nested-span tracer wired through the whole
  pipeline (``solve_many`` and its shape buckets, the host
  decompose/schedule/equalize stages and matchings, scenario periods and
  installs, the served flights' dispatch / collect / install). Disabled it
  costs one attribute check per call site; enabled it exports Chrome
  trace-event JSON viewable in Perfetto (``chrome://tracing``) and writes
  each span into a running JAX profiler's trace.
* ``device`` — the ``jax.named_scope`` names of the fused device
  program's stages, and the map from a compiled program's instructions to
  them (a device trace names only instructions).
* ``timeline_table`` — the time-expanded view of a schedule built on
  ``repro.fabric.timeline``: per-switch occupancy rows (serve /
  reconfigure / idle intervals), per-round utilization, and the makespan
  attribution identity ``transmission + δ paid + idle ≡ s · makespan``
  with an exact lower-bound-gap decomposition per period.
* ``metrics`` — the one metrics vocabulary (log-spaced latency
  histograms, named counters) shared by serving, scenarios, and
  benchmarks; ``repro.serve.metrics`` re-exports it for compatibility.

``python -m repro.obs.dashboard <scenario>`` renders the terminal
timeline; ``--html``/``--trace`` write the HTML report and the Chrome
trace.
"""

from .metrics import (
    STAGES,
    Counters,
    LatencyHistogram,
    ServeMetrics,
    warning_category,
    warning_counts,
)
from .trace import Tracer, get_tracer, span

# timeline_table builds on fabric.timeline (which builds on core.schedule),
# while core/api modules import obs.trace at module load — so its names
# resolve lazily (PEP 562) to keep the tracer importable from anywhere in
# the pipeline without a cycle.
_TIMELINE_NAMES = (
    "Interval",
    "MakespanAttribution",
    "ScenarioAttribution",
    "SwitchRow",
    "TimelineTable",
    "attribute_scenario",
    "timeline_table",
)


def __getattr__(name: str):
    if name in _TIMELINE_NAMES:
        # importlib (not ``from . import``): the submodule shares its name
        # with the ``timeline_table`` function, so a fromlist import would
        # re-enter this __getattr__ forever. Bind every lazy name at once —
        # importing the submodule sets the package attribute
        # ``timeline_table`` to the *module*, which must be overwritten
        # with the function before anyone can see it.
        import importlib

        mod = importlib.import_module(".timeline_table", __name__)
        for lazy in _TIMELINE_NAMES:
            globals()[lazy] = getattr(mod, lazy)
        return globals()[name]
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "Counters",
    "Interval",
    "LatencyHistogram",
    "MakespanAttribution",
    "STAGES",
    "ScenarioAttribution",
    "ServeMetrics",
    "SwitchRow",
    "TimelineTable",
    "Tracer",
    "attribute_scenario",
    "get_tracer",
    "span",
    "timeline_table",
    "warning_category",
    "warning_counts",
]
