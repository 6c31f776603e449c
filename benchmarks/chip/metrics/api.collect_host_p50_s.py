"""Median, over the window's flights, of the host work of collecting a
batch once its device results are ready (``timings["collect_host_s"]``):
transfers, materialization, Eq. 3 validation and the reports."""

import statistics


def read(run):
    per_flight = {}
    for r in run.answered:
        flight = getattr(r.result, "flight", None)
        t = r.result.timings.get("collect_host_s")
        if flight is not None and t is not None:
            per_flight[flight] = t
    return statistics.median(per_flight.values()) if per_flight else None
