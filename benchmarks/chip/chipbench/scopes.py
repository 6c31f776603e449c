"""Device seconds by stage of the served program, for per-layer metrics.

A device trace names an operation only by its instruction
(``fusion.321``). The program names its stages with ``jax.named_scope``
and maps each instruction of its compiled text to one
(``repro.obs.device.op_scopes`` over ``repro.api.jax_backend.
program_text``). ``scope_seconds`` sums the traced self time of each
instruction (``run.reduction.op_s``) by stage.

The compile behind the map runs once per run, when the first metric asks,
which is after the window has closed and the compile meter has been read;
with the persistent cache it is a load. A program without the map (one
older than its stage scopes) gives None.
"""

from __future__ import annotations

import functools
import json
from collections import defaultdict

# DECOMPOSE's matcher call, and the bidding rounds inside it.
MATCH_SCOPES = (
    "spectra.decompose.match",
    "auction.forward_round",
    "auction.reverse_round",
)
UNSCOPED = "unscoped"


@functools.lru_cache(maxsize=None)
def _scope_map(B: int, n: int, s: int, options: str) -> dict | None:
    try:
        from repro.api import SolveOptions
        from repro.api.jax_backend import program_text
        from repro.obs.device import op_scopes
    except ImportError:
        return None
    return op_scopes(program_text(B, n, s,
                                  SolveOptions(**json.loads(options))))


def scope_seconds(run) -> dict[str, float] | None:
    """Traced device self seconds by stage (``UNSCOPED`` for instructions
    outside every stage); None without a trace, an answer or a map."""
    red = run.reduction
    answered = [r for r in run.answered
                if getattr(r.result, "report", None) is not None]
    if red is None or not answered:
        return None
    # The trace holds the window's first flight.
    first = min(answered, key=lambda r: r.t_submit)
    config = run.cell.config
    scope_of = _scope_map(
        int(first.result.report.extras["batch_size"]), int(config["n"]),
        int(config["s"]),
        json.dumps(config["serve"].get("options", {}), sort_keys=True))
    if scope_of is None:
        return None
    out: dict[str, float] = defaultdict(float)
    for name, t in red.op_s.items():
        out[scope_of.get(name, UNSCOPED)] += t
    return dict(out)
