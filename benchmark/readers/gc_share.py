"""Share of the window's wall time that the interpreter's cyclic collector
took (every thread stands still while it runs), in percent; traced runs."""


def read(run, params):
    if not run.gc_pauses or not run.records:
        return None
    window = max(r.end for r in run.records) - run.t0
    return 100.0 * sum(s for _, s in run.gc_pauses) / window
