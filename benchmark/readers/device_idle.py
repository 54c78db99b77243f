"""Share of the traced time in which no operation ran on the device: 1 -
busy over the time from the slice's first device operation to its last
(``trace_reduce.traced_seconds``), the time ``idle_attributed_pct`` splits
by host span."""


def read(run, params):
    if run.trace is None or run.trace["traced_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["traced_s"])
