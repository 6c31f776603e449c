"""Median submit-to-usable time over every request sent in the window
(host clock; an answer that came after the close counts with its wait)."""

import statistics


def read(run):
    lat = [r.t_done - r.t_submit for r in run.answered]
    return statistics.median(lat) if lat else None
