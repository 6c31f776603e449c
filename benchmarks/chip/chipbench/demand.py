"""Demand matrices, by family: ``demand/<family>.py`` holds one family's
generator. A configuration names its family in ``demand.family`` and its
knobs beside it.

The helpers here are copies of the program's ``traffic/workloads.py``
ones, which the family files share.
"""

from __future__ import annotations

import numpy as np

from . import plugins


def normalize_max_line(D: np.ndarray) -> np.ndarray:
    """Scale so the largest row or column sum is 1."""
    T = max(D.sum(1).max(), D.sum(0).max())
    return D / T if T > 0 else D


def add_noise(D: np.ndarray, sigma: float, rng: np.random.Generator):
    """Gaussian noise of std ``sigma`` on the nonzero entries."""
    D = D.copy()
    nz = D > 0
    D[nz] = np.maximum(D[nz] + rng.normal(0.0, sigma, size=int(nz.sum())),
                       1e-9)
    return D


def family(name: str):
    """The generator of family ``name``."""
    return plugins.load("demand", name).generate


def generate(config: dict, rng: np.random.Generator) -> np.ndarray:
    """One demand matrix of ``config``'s family and size."""
    knobs = dict(config["demand"])
    return family(knobs.pop("family"))(rng, n=int(config["n"]), **knobs)
