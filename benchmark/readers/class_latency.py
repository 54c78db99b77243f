"""Median client-side latency of some query classes over the window, ms."""

import statistics


def read(run, params):
    values = [(r.end - r.start) * 1000.0 for r in run.records
              if r.cls in params["classes"]]
    return statistics.median(values) if values else None
