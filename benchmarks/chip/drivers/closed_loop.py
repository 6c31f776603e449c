"""A closed loop of tenants on one server: each tenant keeps one request
in flight and sends its next period's matrix when the last one's answer
comes back. Parameter: ``tenants``."""

from __future__ import annotations

import time
from typing import Any, Callable

import numpy as np

from chipbench.loop import DRAIN_S, Request, warm_rng


def warm_up(server, config: dict, seed: int) -> None:
    """One full batch at the cell's size, the only shape the loop sends.
    Each matrix is one weighted permutation: the same programs compile and
    load as for the cell's demand, but the solve takes one matching round,
    since these answers serve no request."""
    b = int(config["serve"]["max_batch"])
    n = int(config["n"])
    for i in range(b):
        D = np.zeros((n, n))
        D[np.arange(n), warm_rng(seed, i).permutation(n)] = 0.5
        server.submit(f"warm{i}", D)
    while server.step():
        pass
    server.results.clear()


def run(server, make_matrix: Callable[[int, int], np.ndarray],
        traffic: dict, t_start: float, seconds: float,
        annotate: Callable[[str], Any]) -> list[Request]:
    """Drive ``server`` from ``t_start`` for ``seconds``; every request
    sent inside the window, answered or not."""
    tenants = int(traffic["tenants"])
    t_end = t_start + seconds
    t_give_up = t_end + DRAIN_S
    periods = [0] * tenants
    sent: list[Request] = []
    waiting: dict[int, Request] = {}

    def send(tenant: int) -> None:
        D = make_matrix(tenant, periods[tenant])
        with annotate("bench.submit"):
            t = time.perf_counter()
            ticket, _ = server.submit(f"tenant{tenant}", D)
        req = Request(tenant, periods[tenant], t, ticket)
        periods[tenant] += 1
        sent.append(req)
        waiting[ticket] = req

    for tenant in range(tenants):
        send(tenant)
    while waiting and time.perf_counter() < t_give_up:
        with annotate("bench.step"):
            if not server.step():
                break
        now = time.perf_counter()
        for ticket in [t for t in waiting if t in server.results]:
            req = waiting.pop(ticket)
            req.t_done = now
            req.result = server.results.pop(ticket)
            if now < t_end:
                send(req.tenant)
    return sent
