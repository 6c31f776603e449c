"""MoE expert routing (arXiv:2603.07373 Fig. 6b), a copy of the program's
``moe_workload``: the same draws in the same order, so a seed gives the
program's matrix. Each GPU hosts one expert; its tokens are routed top-k
over the other experts, whose popularity is mildly skewed."""

import numpy as np

from chipbench.demand import add_noise, normalize_max_line


def generate(rng: np.random.Generator, *, n: int, top_k: int = 6,
             tokens_per_gpu: int = 8192, skew: float = 0.25,
             noise: float = 0.0) -> np.ndarray:
    pop = 1.0 + skew * np.abs(rng.standard_normal(n))
    pop /= pop.sum()
    D = np.zeros((n, n), dtype=np.float64)
    for src in range(n):
        p = pop.copy()
        p[src] = 0.0
        p /= p.sum()
        D[src, :] = rng.multinomial(tokens_per_gpu * top_k, p)
    D = normalize_max_line(D)
    if noise > 0:
        D = add_noise(D, noise, rng)
    return D
