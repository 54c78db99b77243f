"""Share of the HBM roofline: the least time the chip could take to move
the bytes the slice's queries need (schema widths x rows + result bytes,
``roofline.query_bytes``; blind to the program's plan) over the time the
device was busy in the slice. Bound by bytes, never by operations: these
queries do a handful of integer operations per byte."""

import roofline
from _slice import shares


def read(run, params):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    n_bytes = 0.0
    for r, share in shares(run):
        if r.dispatches:  # an answer from a cache moves nothing
            n_bytes += share * roofline.query_bytes(
                r.sql, run.config, run.total_rows, r.n_rows, r.n_cols)
    if n_bytes <= 0:
        return None
    return 100.0 * roofline.least_seconds(n_bytes, run.peak) \
        / run.trace["busy_s"]
