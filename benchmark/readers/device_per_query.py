"""Device busy time of the traced slice over the requests it holds, ms."""

from _slice import shares


def read(run, params):
    if run.trace is None:
        return None
    n = sum(share for _, share in shares(run))
    return 1000.0 * run.trace["busy_s"] / n if n > 0 else None
