"""Geometric mean, over every schedule answered for the window's requests,
of its reported makespan over the benchmark's §IV lower bound."""

import math


def read(run):
    ratios = [r.result.makespan / run.lbs[(r.tenant, r.period)]
              for r in run.answered if (r.tenant, r.period) in run.lbs]
    if not ratios:
        return None
    return math.exp(sum(math.log(x) for x in ratios) / len(ratios))
