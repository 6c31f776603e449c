"""Median of the server's own queue wait (``ServeResult.timings``) over
the window's answered requests: the serve layer's span."""

import statistics


def read(run):
    waits = [r.result.timings["queue_wait_s"] for r in run.answered]
    return statistics.median(waits) if waits else None
