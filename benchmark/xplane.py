"""The run's own profiler trace, read once per process: the program's
annotations on the host plane, the launches beside them, and the device's
modules and operations with the scope each was traced under.

``jax.profiler.ProfileData`` gives events and their own stats but not the
stats of an event's metadata, and that is where the TPU's operations keep
the name they were traced under (``tf_op``:
``jit(scan_<label>)/vmap(filter)/and``). So the ``.xplane.pb`` is read
here as what it is, a protobuf (``XSpace``, tsl/profiler/protobuf/
xplane.proto), by its wire format: a few dozen lines, nothing to install.

Times: every line's events are nanoseconds since the profile's start on
one clock for host and device, the host's epoch clock (``profile_start_time``
of the ``Task Environment`` plane is ``time.time_ns()`` at the start; the
device's timestamps are mapped onto it by the profiler, to a few tenths of
a millisecond).

Everything below ``trace()`` is a pure function over plain tuples, so a
small hand-built trace checks it (tests/test_span_readers.py):

    host     {thread: [(name, start_ns, dur_ns, stats)]}   stats: a dict
    modules  [(name, start_ns, dur_ns)]                    "jit_x(123)"
    ops      [(name, start_ns, dur_ns, tf_op)]

A program annotation is a host event whose stats hold ``span_id`` (spi/
trace.py enters one per span, with the request's ``query_id``); a launch
is the runtime's own ``PjitFunction(<name>)`` event, which starts module
``jit_<name>``.
"""

from __future__ import annotations

import re
import struct
import sys
from pathlib import Path

HOST_PLANE = "/host:CPU"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LAUNCH = re.compile(r"^PjitFunction\((.*)\)$")
NO_SPAN = "(no span open on the launching thread)"
NO_LAUNCH = "(no launch found for the module)"
SLICE_EDGES = "(before the first and after the last operation of the slice)"
# the device's clock is mapped onto the host's to a few tenths of a
# millisecond: an execution may read as starting that much before its launch
CLOCK_SLACK_NS = 1_000_000


# -- the wire format ---------------------------------------------------------


def _varint(buf, i: int):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if not c & 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, memoryviews
    for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield key >> 3, value


def _signed(x: int) -> int:
    return x - (1 << 64) if x >= 1 << 63 else x


def _stat(buf, stat_names: dict):
    """One XStat as (name, value)."""
    name = value = None
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f in (5, 6):
            value = bytes(v).decode("utf-8", "replace")
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf) -> dict:
    """{name, stats, lines: {line name: [(event name, start_ns, dur_ns,
    event stats, metadata stats)]}}; a host plane has one line a thread,
    keyed ``<name>/<line id>``."""
    name, lines_raw, stats_raw = "", [], []
    event_meta, stat_names = {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            lines_raw.append(v)
        elif f == 4:
            key, value = _map_entry(v)
            event_meta[key] = value
        elif f == 5:
            key, value = _map_entry(v)
            stat_names[key] = next(
                (bytes(x).decode() for g, x in _fields(value) if g == 2), "")
        elif f == 6:
            stats_raw.append(v)
    metas = {}
    for key, raw in event_meta.items():
        label, stats = "", {}
        for f, v in _fields(raw):
            if f == 2:
                label = bytes(v).decode("utf-8", "replace")
            elif f == 5:
                k, x = _stat(v, stat_names)
                stats[k] = x
        metas[key] = (label, stats)
    lines = {}
    for raw in lines_raw:
        line_id, line_name, base_ns, events = 0, "", 0, []
        for f, v in _fields(raw):
            if f == 1:
                line_id = v
            elif f == 2:
                line_name = bytes(v).decode()
            elif f == 3:
                base_ns = v
            elif f == 4:
                events.append(v)
        out = []
        for ev in events:
            meta_id = offset_ps = dur_ps = 0
            stats = {}
            for f, v in _fields(ev):
                if f == 1:
                    meta_id = v
                elif f == 2:
                    offset_ps = v
                elif f == 3:
                    dur_ps = v
                elif f == 4:
                    k, x = _stat(v, stat_names)
                    stats[k] = x
            label, meta_stats = metas.get(meta_id, ("", {}))
            out.append((label, base_ns + offset_ps // 1000, dur_ps // 1000,
                        stats, meta_stats))
        key = line_name if name.startswith(DEVICE_PLANE_PREFIX) \
            else f"{line_name}/{line_id}"
        lines[key] = out
    return {"name": name, "lines": lines,
            "stats": dict(_stat(s, stat_names) for s in stats_raw)}


def read_xplane(path) -> dict:
    """{"start_ns", "stop_ns", "host", "modules", "ops"} of one
    ``.xplane.pb``, in the shapes the functions below take; ``start_ns``
    and ``stop_ns`` are the profile's start and stop on the epoch clock
    (None where the file does not say)."""
    data = memoryview(Path(path).read_bytes())
    host, modules, ops, start_ns, stop_ns = {}, [], [], None, None
    for f, v in _fields(data):
        if f != 1:
            continue
        plane = _plane(v)
        if plane["name"] == HOST_PLANE:
            for thread, events in plane["lines"].items():
                host[thread] = [(n, s, d, st) for n, s, d, st, _ in events]
        elif plane["name"].startswith(DEVICE_PLANE_PREFIX):
            modules += [(n, s, d) for n, s, d, _, _ in
                        plane["lines"].get(MODULES_LINE, [])]
            ops += [(n, s, d, str(meta.get("tf_op") or ""))
                    for n, s, d, _, meta in plane["lines"].get(OPS_LINE, [])]
        elif "profile_start_time" in plane["stats"]:
            start_ns = plane["stats"]["profile_start_time"]
            stop_ns = plane["stats"].get("profile_stop_time")
    return {"start_ns": start_ns, "stop_ns": stop_ns, "host": host,
            "modules": modules, "ops": ops}


_TRACES: dict = {}


def trace(trace_dir):
    """The trace the run wrote under ``trace_dir`` (``run.trace_dir``), read
    once per process. None where the directory holds none."""
    key = str(trace_dir)
    if key not in _TRACES:
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        _TRACES[key] = read_xplane(files[-1]) if files else None
    return _TRACES[key]


# -- pure functions ------------------------------------------------------------


def module_base(name: str) -> str:
    """``jit_scan_x(123)`` -> ``jit_scan_x``: XLA's fingerprint changes
    with every change to the program, the name does not."""
    return name.split("(", 1)[0]


def annotations(host: dict) -> dict:
    """{thread: [(name, start_ns, end_ns)]} of the program's own spans,
    by start; what the runtime records beside them is left out."""
    out = {}
    for thread, events in host.items():
        own = sorted((s, s + d, n) for n, s, d, st in events
                     if "span_id" in st)
        if own:
            out[thread] = [(n, s, e) for s, e, n in own]
    return out


def launches(host: dict) -> list:
    """[(thread, module name, start_ns, end_ns)] by start. The runtime
    records a launch twice, one event inside the other: the outer counts."""
    out = []
    for thread, events in host.items():
        open_until = {}
        for n, s, d, _ in sorted(events, key=lambda e: (e[1], -e[2])):
            m = LAUNCH.match(n)
            if not m:
                continue
            name = "jit_" + m.group(1)
            if s < open_until.get(name, -1):
                continue
            open_until[name] = s + d
            out.append((thread, name, s, s + d))
    return sorted(out, key=lambda x: x[2])


def match_launches(host: dict, modules: list) -> dict:
    """{index into ``modules``: (thread, launch start, launch end)}: the
    nth launch of a name goes to its nth execution, an execution never
    starting before its launch (one launched before the slice began has
    no launch here, one still queued at the slice's end no execution)."""
    runs = {}
    for i, (name, start, _) in sorted(enumerate(modules),
                                      key=lambda x: x[1][1]):
        runs.setdefault(module_base(name), []).append((start, i))
    matched, taken = {}, {}
    for thread, name, start, end in launches(host):
        queue = runs.get(name, [])
        k = taken.get(name, 0)
        while k < len(queue) and queue[k][0] < start - CLOCK_SLACK_NS:
            k += 1
        if k < len(queue):
            matched[queue[k][1]] = (thread, start, end)
            k += 1
        taken[name] = k
    return matched


def launch_to_start_ms(host: dict, modules: list, span: str,
                       module_prefix: str):
    """Mean, over the dispatches in the trace, of the time from the end of
    a ``span`` annotation to the start of the module it launched: how long
    the program waited in the device's queue behind other requests'. None
    where no such pair is in the trace."""
    by_launch = {(t, s): modules[i][1]
                 for i, (t, s, _) in match_launches(host, modules).items()
                 if module_base(modules[i][0]).startswith(module_prefix)}
    waits = []
    for thread, spans in annotations(host).items():
        for name, start, end in spans:
            if name != span:
                continue
            begun = [run for (t, s), run in by_launch.items()
                     if t == thread and start <= s <= end]
            if begun:
                waits.append(max(0, min(begun) - end) / 1e6)
    return sum(waits) / len(waits) if waits else None


def busy_stretches(ops: list) -> list:
    """[(start_ns, end_ns)] of the union of the operations' intervals."""
    out = []
    for _, start, dur, *_ in sorted(ops, key=lambda e: e[1]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], start + dur)
        else:
            out.append([start, start + dur])
    return [(a, b) for a, b in out]


def idle_by_span_and_module(host: dict, modules: list, ops: list) -> dict:
    """{(name, module): idle ns} over the gaps between the device's busy
    stretches (the union of its operations, as ``device_idle_pct`` counts
    busy); ``module`` is the one whose operation ended the gap. A gap that
    a module's start ends goes to the thread that launched the module and
    is split by the innermost span that thread had open (NO_SPAN where
    none, NO_LAUNCH where the launch is not in the trace). A gap between
    two operations of one module's execution is the device's own
    (``(inside <module>)``)."""
    matched = match_launches(host, modules)
    spans = annotations(host)
    runs = sorted((start, start + dur, i)
                  for i, (_, start, dur) in enumerate(modules))
    out = {}

    def add(name, module, ns):
        if ns > 0:
            out[name, module] = out.get((name, module), 0) + ns

    stretches = busy_stretches(ops)
    for (_, lo), (hi, _) in zip(stretches, stretches[1:]):
        # the execution that the operation after the gap belongs to
        run = max((r for r in runs if r[0] <= hi < r[1]), default=None)
        module = module_base(modules[run[2]][0]) if run else ""
        if run is None:
            add(NO_LAUNCH, module, hi - lo)
        elif run[0] <= lo:
            add(f"(inside {module})", module, hi - lo)
        elif run[2] in matched:
            for name, ns in _innermost(spans.get(matched[run[2]][0], []),
                                       lo, hi).items():
                add(name, module, ns)
        else:
            add(NO_LAUNCH, module, hi - lo)
    return out


def idle_table(host: dict, modules: list, ops: list,
               window: tuple = None) -> dict:
    """{name: idle seconds}: ``idle_by_span_and_module`` summed over the
    modules; with ``window`` (start_ns, end_ns) the time before the first
    and after the last operation is listed too."""
    table = {}
    for (name, _), ns in idle_by_span_and_module(host, modules, ops).items():
        table[name] = table.get(name, 0.0) + ns / 1e9
    stretches = busy_stretches(ops)
    if window is not None and stretches:
        for ns in (stretches[0][0] - window[0], window[1] - stretches[-1][1]):
            if ns > 0:
                table[SLICE_EDGES] = table.get(SLICE_EDGES, 0.0) + ns / 1e9
    return table


def idle_gaps(host: dict, modules: list, ops: list, top: int = 10) -> list:
    """[[``<span> before:<module>``, seconds]], longest first: the result
    line's ``breakdown.idle_gaps``, the device's idle time by what the host
    was doing (the span open on the launching thread) and by the module
    whose start ended the gap."""
    ranked = sorted(idle_by_span_and_module(host, modules, ops).items(),
                    key=lambda kv: -kv[1])[:top]
    return [[name if name.startswith("(inside ") or not module else
             f"{name} before:{module}", ns / 1e9]
            for (name, module), ns in ranked]


def _innermost(spans: list, lo: int, hi: int) -> dict:
    """{span name: ns} of [lo, hi) by the innermost of one thread's spans
    open at each instant; spans of one thread nest, so of those open the
    one that started last (and, of two that started together, the one that
    ends first) is innermost."""
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        open_ = [(s, -e, n) for n, s, e in spans if s <= a and e >= b]
        name = max(open_)[2] if open_ else NO_SPAN
        out[name] = out.get(name, 0) + (b - a)
    return out


def attributed_pct(table: dict):
    """Share of the idle time between the slice's first and last operation
    that falls under a span of the program. The slice's edges are listed
    in the table and left out of the share: the device's tracer starts
    after the host's and stops before it, so no operation is recorded
    there, idle or not. None, never 0, where the table has nothing under
    a span: a trace without annotations says nothing about what the host
    did."""
    under = sum(v for k, v in table.items() if not k.startswith("("))
    total = sum(v for k, v in table.items() if k != SLICE_EDGES)
    return 100.0 * under / total if under > 0 and total > 0 else None


def scope_of(tf_op: str) -> str:
    """The program's scope an operation was traced under: the first part
    of its name after the module's own, without the wrapper a transform
    puts around it (``jit(scan_x)/vmap(filter)/and`` -> ``filter``). An
    XLA fusion is one operation and carries the name of its root. Empty
    where the operation lies under no scope."""
    parts = tf_op.split("/")
    if len(parts) < 3:  # the module's name and the operation's own
        return ""
    part = parts[1]
    while True:
        m = re.match(r"^\w+\((.*)\)$", part)
        if not m or part.startswith("jit("):
            break
        part = m.group(1)
    return "" if part.startswith("jit(") or "(" in part else part


def seconds_by_scope(ops: list) -> dict:
    out = {}
    for _, _, dur, tf_op in ops:
        scope = scope_of(tf_op)
        out[scope] = out.get(scope, 0.0) + dur / 1e9
    return out


def seconds_outside_scopes(ops: list) -> dict:
    """{traced name: seconds} of the operations under no scope: what the
    scopes of the program do not cover. An operation the compiler made
    itself (a layout copy, the loop of an expanded gather) has no traced
    name and goes by its own (``%while.1``)."""
    out = {}
    for name, _, dur, tf_op in ops:
        if not scope_of(tf_op):
            key = tf_op or name.split(" = ", 1)[0]
            out[key] = out.get(key, 0.0) + dur / 1e9
    return out


def say_table(title: str, table: dict, top: int = 12) -> None:
    print(f"[bench] {title}", file=sys.stderr)
    for name, seconds in sorted(table.items(), key=lambda kv: -kv[1])[:top]:
        print(f"[bench]   {seconds:10.6f} s  {name or '(no scope)'}",
              file=sys.stderr)
    sys.stderr.flush()
