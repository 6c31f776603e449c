"""The m-permutation benchmark (arXiv:2603.07373 Fig. 9), a copy of the
program's ``benchmark_workload``: the same draws in the same order. m
random permutation flows per port; ``num_big`` (m/4 unless given) share
``big_frac`` of the bandwidth, the rest share the remainder, plus
Gaussian noise on the nonzeros."""

import numpy as np

from chipbench.demand import add_noise


def generate(rng: np.random.Generator, *, n: int, m: int = 16,
             num_big: int | None = None, big_frac: float = 0.7,
             noise: float = 0.003) -> np.ndarray:
    if num_big is None:
        num_big = max(1, m // 4)
    if m < num_big:
        raise ValueError("m must be at least num_big")
    D = np.zeros((n, n), dtype=np.float64)
    big_w = big_frac / num_big
    small_w = (1.0 - big_frac) / max(m - num_big, 1)
    for f in range(m):
        D[np.arange(n), rng.permutation(n)] += big_w if f < num_big else small_w
    return add_noise(D, noise, rng)
