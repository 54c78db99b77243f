"""Mean over the traced requests of (sum of ``plus`` spans) - (sum of
``minus`` spans), in ms. A span is named by its ``operator`` in the
response's ``trace_info``; ``client_wall`` is the request's client-side
latency. Requests without ``trace_info`` (an untraced run) give nothing.

A mean, not a median: a mix of a cheap and a dear class puts the median of
a span on the edge between the two, where it is a coin toss; means add up
to the mean client latency, layer by layer."""


def _sum(record, names) -> float:
    total = 0.0
    for name in names:
        if name == "client_wall":
            total += (record.end - record.start) * 1000.0
        else:
            total += sum(s["durationMs"] for s in record.trace
                         if s.get("operator") == name)
    return total


def read(run, params):
    values = [_sum(r, params["plus"]) - _sum(r, params.get("minus", []))
              for r in run.records if r.trace]
    return sum(values) / len(values) if values else None
