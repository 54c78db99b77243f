"""``span_sum`` over the spans' ``cpuMs``, the CPU time of the thread that
opened each: the mean over the traced requests of (sum of ``plus`` spans'
``cpuMs``) - (sum of ``minus`` spans'), in ms. Beside the same sum over
``durationMs`` it splits a layer's wall time into work and waiting (for
the interpreter, the OS, a lock). A span without ``cpuMs`` counts 0; None,
never 0, where no traced span of a ``plus`` name carries it (a program
from before spans carried it, or a window in which nothing took that
path)."""


def _sum(record, names) -> float:
    return sum(s.get("cpuMs", 0.0) for s in record.trace
               if s.get("operator") in names)


def read(run, params):
    plus, minus = params["plus"], params.get("minus", [])
    traced = [r for r in run.records if r.trace]
    if not any("cpuMs" in s and s.get("operator") in plus
               for r in traced for s in r.trace):
        return None
    values = [_sum(r, plus) - _sum(r, minus) for r in traced]
    return sum(values) / len(values)
