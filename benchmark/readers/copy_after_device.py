"""Mean over the traced slice's ``span`` annotations (``DEVICE_COPY``) of
the part of each that follows the device's work, in ms: from the later of
the span's start and the end of the last device execution, of a module
launched from the span's own thread, that ends before the span does, to
the span's end. A fetch's copy blocks until its results exist; what is
left once the program that made them (its pack) has ended is the crossing
and the host's copy itself. None where the run made no trace, or where the
trace holds no such annotation (a program from before the span existed)."""

import bisect

import xplane


def read(run, params):
    if run.trace is None:
        return None
    trace = xplane.trace(run.trace_dir)
    if trace is None:
        return None
    return copy_after_device_ms(trace["host"], trace["modules"],
                                params["span"])


def copy_after_device_ms(host: dict, modules: list, span: str):
    ends = {}  # thread: the ends of the executions it launched, in order
    for i, (thread, _, _) in xplane.match_launches(host, modules).items():
        ends.setdefault(thread, []).append(modules[i][1] + modules[i][2])
    for done in ends.values():
        done.sort()
    after = []
    for thread, spans in xplane.annotations(host).items():
        done = ends.get(thread, [])
        for name, start, end in spans:
            if name != span:
                continue
            k = bisect.bisect_left(done, end)
            last = done[k - 1] if k else start
            after.append((end - max(start, last)) / 1e6)
    return sum(after) / len(after) if after else None
