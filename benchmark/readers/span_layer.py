"""``span_sum`` for a layer that is defined by spans of its own: the mean
over the traced requests of (sum of ``plus`` spans) - (sum of ``minus``
spans), in ms, and nothing where no request's trace holds every span named
in ``needs`` — a program from before those spans existed, or a window in
which nothing took that path. (``span_sum`` alone would read such a trace
as 0 ms of the layer, or as the whole of what the layer is subtracted
from.)"""

import span_sum


def read(run, params):
    have = {s.get("operator") for r in run.records if r.trace
            for s in r.trace}
    if not set(params["needs"]) <= have:
        return None
    return span_sum.read(run, params)
