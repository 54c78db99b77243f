"""From the profiler's trace to two things: when the device was busy, and
which XLA module the busy time belongs to. What is finer (scopes, host
spans on the profiler's clock) is ``xplane.py``'s.

Pure functions over (name, start_ns, duration_ns) events so that a small
hand-built trace checks them; ``read_xplane`` is the only part that touches
the profiler's file format.
"""

from __future__ import annotations

from pathlib import Path

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union_seconds(events: list) -> float:
    """Length of the union of the events' intervals, in seconds."""
    busy, end = 0, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            busy += dur
            end = stop
        elif stop > end:
            busy += stop - end
            end = stop
    return busy / 1e9


def traced_seconds(events: list) -> float:
    """From the start of the first event to the end of the last: the time
    the device's tracer covered, and so the time that busy seconds are a
    share of. (The profile's own window, from the call that starts it to
    the end of the call that stops it, is about a third of a second
    longer and holds no operation there; the host's slice between those
    two calls lies inside the traced time.)"""
    if not events:
        return 0.0
    return (max(start + dur for _, start, dur in events)
            - min(start for _, start, _ in events)) / 1e9


def seconds_by_name(events: list, top: int = 10) -> list:
    sums = {}
    for name, _, dur in events:
        sums[name] = sums.get(name, 0) + dur
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def reduce_planes(planes: dict) -> dict:
    """``planes``: {plane name: {line name: [(name, start_ns, dur_ns)]}}
    for the device planes only. Busy and traced seconds are averaged over
    the chips."""
    if not planes:
        raise ValueError("the trace holds no device plane")
    busy, traced, ops, modules = [], [], [], []
    for name, lines in sorted(planes.items()):
        if OPS_LINE not in lines:
            raise ValueError(f"plane {name} has no {OPS_LINE!r} line; lines: "
                             f"{sorted(lines)}")
        busy.append(union_seconds(lines[OPS_LINE]))
        traced.append(traced_seconds(lines[OPS_LINE]))
        ops += lines[OPS_LINE]
        modules += lines.get(MODULES_LINE, [])
    return {
        "busy_s": sum(busy) / len(busy),
        "traced_s": sum(traced) / len(traced),
        "chips": len(busy),
        "device_ops": seconds_by_name(ops),
        "device_modules": seconds_by_name(modules),
    }


def read_xplane(log_dir) -> dict:
    """The device planes of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    profile = ProfileData.from_file(str(files[-1]))
    planes = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        planes[plane.name] = {
            line.name: [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
            for line in plane.lines}
    return planes
