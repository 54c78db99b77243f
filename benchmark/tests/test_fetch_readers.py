"""The readers of the fetch's copy and of the spans' CPU time, on
`test_span_readers.py`'s hand-built trace with each DEVICE_FETCH split in
its two parts, and on hand-built span lists — and None wherever there is
nothing to read (an untraced run, the parent's traces, which carry neither
DEVICE_COPY nor `cpuMs`)."""

import types

import pytest

import xplane
from test_span_readers import HOST, MODULES, MS, OPS, WARM_OP, _reader, \
    _record, ann


# -- the fetch's copy once the device is done, and the spans' CPU time ---------

# the hand-built trace with each DEVICE_FETCH split in two: A's copy 22-93
# (its own program ends at 60; B's, at 90, is another thread's) and decode
# 93-95, a second copy of A's at 96-97 with the device long done; B's copy
# 34-110 (its program ends at 90) and decode 110-125
HOST_COPY = {
    "python/1": HOST["python/1"] + [ann("DEVICE_COPY", 22, 93, 5),
                                    ann("FETCH_DECODE", 93, 95, 6),
                                    ann("DEVICE_COPY", 96, 97, 7)],
    "python/2": HOST["python/2"] + [ann("DEVICE_COPY", 34, 110, 4),
                                    ann("FETCH_DECODE", 110, 125, 5)],
}


def test_copy_after_device_reads_the_copy_once_the_threads_program_ended(
        monkeypatch):
    reader = _reader("copy_after_device")
    assert reader.copy_after_device_ms(HOST_COPY, MODULES, "DEVICE_COPY") \
        == pytest.approx((33 + 1 + 20) / 3)
    # the parent's trace: a DEVICE_FETCH, no DEVICE_COPY inside it
    assert reader.copy_after_device_ms(HOST, MODULES, "DEVICE_COPY") is None
    monkeypatch.setattr(xplane, "_TRACES", {"here": {
        "start_ns": 0, "stop_ns": 151 * MS, "host": HOST_COPY,
        "modules": MODULES, "ops": OPS + WARM_OP}})
    run = types.SimpleNamespace(trace={"busy_s": 0.074}, slice=(0.0, 0.2),
                                trace_dir="here", records=[])
    params = {"span": "DEVICE_COPY"}
    assert reader.read(run, params) == pytest.approx(18.0)
    # the gap that ended at B's pad now lies under the copy and the decode
    table = xplane.idle_table(HOST_COPY, MODULES, OPS + WARM_OP)
    assert table["DEVICE_COPY"] == pytest.approx(0.020)
    assert table["FETCH_DECODE"] == pytest.approx(0.015)
    assert "DEVICE_FETCH" not in table
    # an untraced run, and a traced one whose trace file is missing
    run.trace = None
    assert reader.read(run, params) is None
    monkeypatch.setattr(xplane, "_TRACES", {"here": None})
    run.trace = {"busy_s": 0.074}
    assert reader.read(run, params) is None


def _span(name, wall, cpu=None):
    out = {"operator": name, "durationMs": wall}
    if cpu is not None:
        out["cpuMs"] = cpu
    return out


def test_span_cpu_means_plus_minus_over_cpu_time():
    params = {"plus": ["QUERY_PROCESSING"],
              "minus": ["SCHEDULER_WAIT", "family_dispatch", "DEVICE_FETCH",
                        "SERVER_COMBINE"]}
    run = types.SimpleNamespace(records=[
        _record(0, 1, [_span("QUERY_PROCESSING", 30.0, 10.0),
                       _span("family_dispatch", 4.0, 3.0),
                       _span("DEVICE_FETCH", 20.0, 2.0),
                       _span("BROKER_REDUCE", 1.0, 1.0)]),
        _record(0, 1, [_span("QUERY_PROCESSING", 12.0, 8.0),
                       _span("SCHEDULER_WAIT", 1.0, 0.5),
                       # a span recorded in one shot: no CPU time, counts 0
                       _span("SERVER_COMBINE", 2.0)]),
        _record(0, 1, None)])
    assert _reader("span_cpu").read(run, params) == pytest.approx(
        ((10 - 3 - 2) + (8 - 0.5)) / 2)
    # the same spans' wall time, as server_self_ms reads it
    assert _reader("span_layer").read(
        run, dict(params, needs=["QUERY_PROCESSING"])) \
        == pytest.approx(((30 - 4 - 20) + (12 - 1 - 2)) / 2)
    # the parent's traces: the spans, no cpuMs
    bare = types.SimpleNamespace(records=[
        _record(0, 1, [_span("QUERY_PROCESSING", 30.0),
                       _span("DEVICE_FETCH", 20.0)])])
    assert _reader("span_cpu").read(bare, params) is None
    # cpuMs on other spans, none on the one it reads; and no trace at all
    other = types.SimpleNamespace(records=[
        _record(0, 1, [_span("family_dispatch", 4.0, 3.0)])])
    assert _reader("span_cpu").read(other, {"plus": ["GATHER_STACK"]}) is None
    assert _reader("span_cpu").read(
        types.SimpleNamespace(records=[_record(0, 1, None)]), params) is None
