"""Median, over the window's answered requests, of the bidding rounds the
matcher bid to decompose the request's matrix (the program's counter
``extras["bid_rounds"]``)."""

import statistics


def read(run):
    rounds = []
    for r in run.answered:
        report = getattr(r.result, "report", None)
        if report is not None and "bid_rounds" in report.extras:
            rounds.append(report.extras["bid_rounds"])
    return statistics.median(rounds) if rounds else None
