"""Share of the device's idle time, between the first and the last
operation of the traced slice, that falls under a span of the program.
Idle is the time outside the union of the device's operations, as
``device_idle_pct`` counts it. Each gap that a module's start ends goes to
the thread whose launch started the module and is split by the innermost
span that thread had open; gaps inside one execution of a module are the
device's own. The profile's two edges, before the first and after the last
operation, are printed with the table and left out of the share, as
``device_idle_pct`` leaves them out: the device's tracer records nothing
there. The whole table is printed on standard error. None, never 0, when
the trace holds no annotation of the program's."""

import xplane


def read(run, params):
    if run.trace is None:
        return None
    trace = xplane.trace(run.trace_dir)
    if trace is None:
        return None
    window = None
    if trace["start_ns"] is not None and trace["stop_ns"] is not None:
        window = (0, trace["stop_ns"] - trace["start_ns"])
    table = xplane.idle_table(trace["host"], trace["modules"], trace["ops"],
                              window)
    pct = xplane.attributed_pct(table)
    if pct is not None:
        xplane.say_table("device idle in the traced slice, by the span open "
                         "on the thread whose launch ended the gap", table)
    return pct
