"""Mean over the traced requests of an attribute summed over the request's
spans of one name (``hostFetches`` of ``DEVICE_FETCH``: how often a request
crossed from the device to the host). Requests without ``trace_info``, and
a trace in which no span of that name carries the attribute, give nothing."""


def read(run, params):
    values, seen = [], False
    for r in run.records:
        if not r.trace:
            continue
        found = [s["attributes"][params["attribute"]] for s in r.trace
                 if s.get("operator") == params["span"]
                 and params["attribute"] in (s.get("attributes") or {})]
        seen = seen or bool(found)
        values.append(sum(found))
    return sum(values) / len(values) if seen else None
