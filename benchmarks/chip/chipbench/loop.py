"""What every traffic driver shares: the seeded generators of the
matrices and the record of one request.

A traffic mix is a data file, ``traffic/<name>.json``, that names its
driver (``drivers/<driver>.py``) and that driver's parameters. A driver
has ``warm_up(server, config, seed)``, which runs every shape the window
will send once, and ``run(server, make_matrix, traffic, t_start, seconds,
annotate)``, which returns every request sent inside the window. Requests
sent inside the window are waited for up to ``DRAIN_S`` past its close;
an answer that comes after the close still counts, with its wait.

Request ``(tenant, period)`` carries the matrix drawn from the seed, the
tenant and the period, so no matrix repeats within a run and the same
seed sends the same matrices. A driver times every request on the host
clock from just before ``submit`` until the ``step`` that returned its
answer.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import numpy as np

DRAIN_S = 60.0


def matrix_rng(seed: int, tenant: int, period: int) -> np.random.Generator:
    """The generator of request ``(tenant, period)`` under ``seed``."""
    return np.random.default_rng(np.random.SeedSequence([seed, 0, tenant,
                                                         period]))


def warm_rng(seed: int, i: int) -> np.random.Generator:
    """The generator of the ``i``-th warm-up matrix under ``seed``."""
    return np.random.default_rng(np.random.SeedSequence([seed, 1, i]))


@dataclass
class Request:
    tenant: int
    period: int
    t_submit: float
    ticket: int
    t_done: float | None = None
    result: Any = None
