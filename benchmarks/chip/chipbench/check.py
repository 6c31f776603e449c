"""The comparison that decides ``correct``, and the control it must fail.

Every answer served for a request sent in the window is checked:

* ``unanswered``: requests that got no answer by the end of the drain;
* ``malformed``: schedules that are not ``s`` switches of permutations
  with finite nonnegative weights;
* ``cover_gap``: the largest uncovered demand, ``max(D - coverage)``, over
  ``max(D)`` (Eq. 3);
* ``makespan_err``: the largest gap between the makespan the server
  reported and the one its schedule has, over the latter;
* ``below_lb``: schedules shorter than the §IV lower bound (impossible for
  a schedule that covers its demand);
* ``host_gap_q1`` (where the configuration sets its limit): the first
  quartile over the answers of ``|makespan / reference makespan - 1|``,
  against the host SPECTRA reference. Not the largest: the device matcher
  is an ε-optimal auction, and on a near-tie it picks another matching
  than the host's exact one, by design, on some answers of a run
  (PERF.md). A quartile holds unless most answers depart, as every one
  does in a lower precision. It judges quality only: the numbers above
  hold every answer to its demand;
* ``window_compiles``: compiles and persistent-cache reads in the window.

The limits of the counts are 0. The others come from the configuration's
``limits``; ``PERF.md`` gives the readings each was set from.
"""

from __future__ import annotations

from dataclasses import dataclass
from types import SimpleNamespace
from typing import Callable

import numpy as np

from . import reference as ref

EXACT = ("unanswered", "malformed", "below_lb", "window_compiles")
# A schedule may reach its bound; a float64 sum may miss it by rounding.
LB_SLACK = 1e-9


@dataclass
class Answer:
    """One served answer, as the benchmark reads it."""

    tenant: int
    period: int
    switches: tuple  # per switch: [(perm, alpha), ...]
    makespan: float  # as reported by the server


def answer_of(req) -> Answer:
    """The schedule and makespan a served result carries."""
    res = req.result
    switches = tuple(
        [(np.asarray(p), float(a)) for p, a in zip(sw.perms, sw.alphas)]
        for sw in res.report.schedule.switches
    )
    return Answer(req.tenant, req.period, switches, float(res.makespan))


def check(answers: list[Answer], demand_of: Callable[[Answer], np.ndarray],
          config: dict, *, unanswered: int,
          window_compiles: int) -> tuple[dict, list[float], dict]:
    """Readings of every number, each answer's §IV bound, and notes for
    the log (numbers that are looked at but not compared)."""
    s, delta = int(config["s"]), float(config["delta"])
    limits = config["limits"]
    r = {"unanswered": unanswered, "malformed": 0, "cover_gap": 0.0,
         "makespan_err": 0.0, "below_lb": 0,
         "window_compiles": window_compiles}
    lbs: list[float] = []
    for a in answers:
        D = demand_of(a)
        n = D.shape[0]
        lb = ref.lower_bound(D, s, delta)
        lbs.append(lb)
        if ref.malformed(a.switches, n, s) is not None:
            r["malformed"] += 1
            continue
        gap = float((D - ref.coverage(a.switches, n)).max()) / float(D.max())
        r["cover_gap"] = max(r["cover_gap"], gap)
        mk = ref.makespan(a.switches, delta)
        r["makespan_err"] = max(r["makespan_err"], abs(a.makespan - mk) / mk)
        if mk < lb * (1 - LB_SLACK):
            r["below_lb"] += 1
    notes: dict = {}
    if "host_gap_q1" in limits:
        gaps = host_gaps(answers, demand_of, config)
        r["host_gap_q1"] = float(np.percentile(gaps, 25)) if gaps else 0.0
        notes = {"host_gap_max": max(gaps, default=0.0),
                 "host_gap_p50": float(np.median(gaps)) if gaps else 0.0,
                 "host_gaps_over_1e-6": sum(g > 1e-6 for g in gaps),
                 "host_gaps_of": len(gaps),
                 "host_gaps": [float(f"{g:.3e}") for g in sorted(gaps)]}
    return r, lbs, notes


def host_gaps(answers, demand_of, config) -> list[float]:
    """``|makespan / reference makespan - 1|`` of each well-formed answer
    (``malformed`` counts the others)."""
    s, delta = int(config["s"]), float(config["delta"])
    gaps = []
    for a in answers:
        D = demand_of(a)
        if ref.malformed(a.switches, D.shape[0], s) is None:
            host = ref.makespan(ref.spectra(D, s, delta), delta)
            gaps.append(abs(ref.makespan(a.switches, delta) / host - 1))
    return gaps


def verdict(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number beside its limit."""
    out = {}
    for name, value in readings.items():
        limit = 0 if name in EXACT else float(limits[name])
        out[name] = {"value": value, "limit": limit}
    ok = all(v["value"] <= v["limit"] for v in out.values())
    return ok, out


class ReferenceServer:
    """The control: the host reference in the program's place, with every
    stored number rounded to ``dtype`` (bfloat16, the precision below the
    float32 the configurations state). Same ``submit`` / ``step`` /
    ``results`` surface as the served path; answers ``max_batch`` requests
    per step."""

    def __init__(self, s: int, delta: float, max_batch: int, dtype):
        self.s, self.delta, self.max_batch, self.dtype = s, delta, max_batch, dtype
        self.queue: list = []
        self.results: dict = {}
        self._next = 0

    def submit(self, tenant: str, D: np.ndarray):
        ticket = self._next
        self._next += 1
        self.queue.append((ticket, D))
        return ticket, "admit"

    def step(self) -> bool:
        batch, self.queue = self.queue[: self.max_batch], self.queue[self.max_batch:]
        for ticket, D in batch:
            sw = ref.spectra(D, self.s, self.delta, self.dtype)
            mk = float(np.asarray(ref.makespan(sw, self.delta),
                                  dtype=self.dtype))
            self.results[ticket] = SimpleNamespace(
                makespan=mk,
                timings={"queue_wait_s": 0.0},
                report=SimpleNamespace(schedule=SimpleNamespace(switches=[
                    SimpleNamespace(perms=[p for p, _ in w],
                                    alphas=[a for _, a in w]) for w in sw
                ])),
            )
        return bool(batch)
