"""Stage names of the device program, and where each compiled op sits.

The fused SPECTRA program wraps each of its stages in ``jax.named_scope``
with one of the names in ``SCOPES``; XLA keeps the scope path in the
``op_name`` metadata of every instruction of the optimized HLO. A profiler
trace names a device operation only by its instruction (``fusion.321``),
so ``op_scopes`` reads the compiled text to map each instruction to its
stage. ``repro.api.jax_backend.program_text`` gives the text of exactly
the program a served batch runs.
"""

from __future__ import annotations

import re

SCOPES = (
    # DECOMPOSE's matcher call, and ``auction``'s bidding rounds inside it
    # (``auction_fr``'s rounds carry no scope of their own).
    "spectra.decompose.match",
    "auction.forward_round",
    # DECOMPOSE's bonus matrix, α and residual updates around the matcher.
    "spectra.decompose.peel",
    "spectra.refine",
    "spectra.repair",
    "spectra.lpt",
    "spectra.equalize",
    "spectra.lb",
    # Stages of the online step that the stateless program does not have.
    "online.warm_refine",
    "online.reuse_lpt",
    "online.credit",
)

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?(\S+)\s+=\s.*?\bop_name="([^"]*)"', re.MULTILINE
)
# A scope is one component of the op_name path; a transform may wrap it,
# as in ``vmap(spectra.lb)``.
_SCOPE = re.compile(
    r"(?<![\w.])(" + "|".join(re.escape(s) for s in SCOPES) + r")(?![\w.])"
)


def op_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: innermost scope of SCOPES}`` over the
    instructions of ``hlo_text`` whose ``op_name`` holds one."""
    out = {}
    for m in _INSTRUCTION.finditer(hlo_text):
        found = _SCOPE.findall(m.group(2))
        if found:
            out[m.group(1)] = found[-1]
    return out
