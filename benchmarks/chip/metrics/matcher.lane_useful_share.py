"""Share of a batch's bidding rounds that its lanes needed: per flight,
the lanes' own rounds (``extras["bid_rounds"]``) summed, over B times the
rounds the batched loops ran (the most ``extras["bid_rounds_run"]`` of
its lanes); the median over the window's flights. A vmapped loop runs
until its slowest lane stops, so 1 means no lane waited."""

import statistics
from collections import defaultdict


def read(run):
    flights = defaultdict(list)
    for r in run.answered:
        report = getattr(r.result, "report", None)
        flight = getattr(r.result, "flight", None)
        if flight is None or report is None or "bid_rounds" not in report.extras:
            continue
        flights[flight].append(report.extras)
    shares = []
    for lanes in flights.values():
        ran = max(e["bid_rounds_run"] for e in lanes)
        if ran > 0:
            shares.append(sum(e["bid_rounds"] for e in lanes)
                          / (len(lanes) * ran))
    return statistics.median(shares) if shares else None
