"""Device time of the operations traced under the program's scopes
``params["scopes"]`` (``jax.named_scope`` in ops/kernels.py), over the
requests the traced slice holds, ms — counted as ``device_ms_per_query``
counts them. An XLA fusion is one operation and counts for the scope of
its root. None where no operation of the trace lies under any of them
(a program without the scopes); the whole table by scope is printed on
standard error once."""

import xplane
from _slice import shares

_SAID = []


def read(run, params):
    if run.trace is None:
        return None
    trace = xplane.trace(run.trace_dir)
    if trace is None:
        return None
    by_scope = xplane.seconds_by_scope(trace["ops"])
    n = sum(share for _, share in shares(run))
    seconds = sum(by_scope.get(s, 0.0) for s in params["scopes"])
    if seconds <= 0 or n <= 0:
        return None
    if not _SAID:
        _SAID.append(True)
        xplane.say_table("device seconds in the traced slice by scope",
                         by_scope)
        xplane.say_table("of those under no scope, by traced name",
                         xplane.seconds_outside_scopes(trace["ops"]), top=6)
    return 1000.0 * seconds / n
