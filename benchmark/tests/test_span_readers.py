"""The readers this PR adds, each on a small hand-built trace: two client
threads, one launch that waits in the device's queue, one idle gap under
GATHER_STACK and one under no span at all — and None wherever there is
nothing to read (an untraced run, the parent's trace without annotations)."""

import struct
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
for p in (str(HERE), str(HERE / "readers")):
    if p not in sys.path:
        sys.path.insert(0, p)

import xplane  # noqa: E402
from run import module_at  # noqa: E402

MS = 1_000_000


def ann(name, start_ms, end_ms, span_id):
    return (name, start_ms * MS, (end_ms - start_ms) * MS,
            {"query_id": "q", "span_id": span_id})


def launch(name, start_ms, end_ms):
    # the runtime records a launch twice, one inside the other
    return [(f"PjitFunction({name})", start_ms * MS, (end_ms - start_ms) * MS,
             {}),
            (f"PjitFunction({name})", start_ms * MS + 1000,
             (end_ms - start_ms) * MS - 2000, {})]


# thread A: QUERY_PROCESSING 0-100 over family_dispatch 10-22 (GATHER_STACK
# 10-20, launch 20-22), DEVICE_FETCH 22-95. Its program runs 22-60.
# thread B: QUERY_PROCESSING 30-130 over family_dispatch 30-34 (launch
# 32-34): queued behind A's program, it runs 60-90; then nothing open on B
# from 130 until a bare launch at 150 (a pad) that runs 150-151.
HOST = {
    "python/1": [ann("QUERY_PROCESSING", 0, 100, 1),
                 ann("family_dispatch", 10, 22, 2),
                 ann("GATHER_STACK", 10, 20, 3),
                 *launch("scan_agg_x", 20, 22),
                 ann("DEVICE_FETCH", 22, 95, 4),
                 ("PJRT_LoadedExecutable_Execute", 21 * MS, MS, {})],
    "python/2": [ann("QUERY_PROCESSING", 30, 130, 1),
                 ann("family_dispatch", 30, 34, 2),
                 *launch("scan_agg_x", 32, 34),
                 ann("DEVICE_FETCH", 34, 125, 3),
                 *launch("_pad", 150, 151)],
}
MODULES = [
    ("jit_warm(7)", 0, 5 * MS),              # launched before the slice
    ("jit_scan_agg_x(99)", 22 * MS, 38 * MS),
    ("jit_scan_agg_x(99)", 60 * MS, 30 * MS),
    ("jit__pad(5)", 150 * MS, 1 * MS),
]
OPS = [
    ("%fusion.1", 22 * MS, 8 * MS, "jit(scan_agg_x)/vmap(filter)/and:"),
    ("%fusion.2", 30 * MS, 30 * MS, "jit(scan_agg_x)/vmap(aggregate)/mul:"),
    ("%fusion.1", 60 * MS, 8 * MS, "jit(scan_agg_x)/vmap(filter)/and:"),
    ("%fusion.2", 68 * MS, 22 * MS, "jit(scan_agg_x)/vmap(aggregate)/mul:"),
    ("%copy", 150 * MS, 1 * MS, "jit(_pad)/pad:"),
]


WARM_OP = [("%warm", 0, 5 * MS, "jit(warm)/x:")]


def test_launches_count_once_and_match_in_order():
    found = xplane.launches(HOST)
    assert [(t, n, s // MS) for t, n, s, _ in found] == [
        ("python/1", "jit_scan_agg_x", 20), ("python/2", "jit_scan_agg_x", 32),
        ("python/2", "jit__pad", 150)]
    matched = xplane.match_launches(HOST, MODULES)
    assert {i: t for i, (t, _, _) in matched.items()} == {
        1: "python/1", 2: "python/2", 3: "python/2"}


def test_launch_to_start_is_the_wait_in_the_queue():
    # A: launched inside 10-22, runs at 22: no wait. B: its dispatch ends
    # at 34, its program starts at 60: 26 ms. Mean 13.
    assert xplane.launch_to_start_ms(
        HOST, MODULES, "family_dispatch", "jit_scan_") == pytest.approx(13.0)
    assert xplane.launch_to_start_ms(
        HOST, MODULES, "family_dispatch", "jit_other_") is None
    bare = {t: [e for e in evs if "span_id" not in e[3]]
            for t, evs in HOST.items()}
    assert xplane.launch_to_start_ms(
        bare, MODULES, "family_dispatch", "jit_scan_") is None


def test_idle_gaps_go_to_the_launching_threads_innermost_span():
    table = xplane.idle_table(HOST, MODULES, OPS + WARM_OP)
    # gap 5-22, ended by A's launch: 5-10 under QUERY_PROCESSING, 10-20
    # under GATHER_STACK, 20-22 under family_dispatch. Gap 90-150, ended by
    # B's pad: 90-125 under DEVICE_FETCH, 125-130 under QUERY_PROCESSING,
    # 130-150 under nothing.
    assert table == {
        "QUERY_PROCESSING": pytest.approx(0.010),
        "GATHER_STACK": pytest.approx(0.010),
        "family_dispatch": pytest.approx(0.002),
        "DEVICE_FETCH": pytest.approx(0.035),
        xplane.NO_SPAN: pytest.approx(0.020)}
    assert xplane.attributed_pct(table) == pytest.approx(100 * 57 / 77)
    # the slice's edges, and a gap between two operations of one execution
    holed = [op for op in OPS + WARM_OP if op[1] != 30 * MS] \
        + [("%fusion.2", 32 * MS, 28 * MS, "jit(scan_agg_x)/aggregate/mul:")]
    table = xplane.idle_table(HOST, MODULES, holed, (-2 * MS, 160 * MS))
    assert table["(inside jit_scan_agg_x)"] == pytest.approx(0.002)
    assert table[xplane.SLICE_EDGES] == pytest.approx(0.002 + 0.009)
    # the edges are listed, and left out of the share
    assert xplane.attributed_pct(table) == pytest.approx(100 * 57 / 79)
    # an operation of a module whose launch the trace does not hold
    orphan = MODULES + [("jit_other(1)", 200 * MS, MS)]
    table = xplane.idle_table(
        HOST, orphan, OPS + WARM_OP + [("%x", 200 * MS, MS, "jit(other)/x:")])
    assert table[xplane.NO_LAUNCH] == pytest.approx(0.049)
    # the parent's trace: launches, but no annotation of the program's
    bare = {t: [e for e in evs if "span_id" not in e[3]]
            for t, evs in HOST.items()}
    table = xplane.idle_table(bare, MODULES, OPS + WARM_OP)
    assert set(table) == {xplane.NO_SPAN}
    assert xplane.attributed_pct(table) is None
    assert xplane.attributed_pct({}) is None


def test_breakdown_gaps_carry_the_host_span_and_the_module_that_ended_them():
    holed = [op for op in OPS + WARM_OP if op[1] != 30 * MS] \
        + [("%fusion.2", 32 * MS, 28 * MS, "jit(scan_agg_x)/aggregate/mul:")]
    orphan = MODULES + [("jit_other(1)", 200 * MS, MS)]
    gaps = xplane.idle_gaps(HOST, orphan,
                            holed + [("%x", 200 * MS, MS, "jit(other)/x:")])
    assert gaps == [
        [f"{xplane.NO_LAUNCH} before:jit_other", pytest.approx(0.049)],
        ["DEVICE_FETCH before:jit__pad", pytest.approx(0.035)],
        [f"{xplane.NO_SPAN} before:jit__pad", pytest.approx(0.020)],
        ["GATHER_STACK before:jit_scan_agg_x", pytest.approx(0.010)],
        ["QUERY_PROCESSING before:jit_scan_agg_x", pytest.approx(0.005)],
        ["QUERY_PROCESSING before:jit__pad", pytest.approx(0.005)],
        ["family_dispatch before:jit_scan_agg_x", pytest.approx(0.002)],
        ["(inside jit_scan_agg_x)", pytest.approx(0.002)]]
    assert len(xplane.idle_gaps(HOST, orphan, holed, top=3)) == 3


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(scan_x)/vmap(filter)/and:", "filter"),
    ("jit(scan_x)/group_by_dense/jit(_where)/select_n:", "group_by_dense"),
    ("jit(scan_x)/shard_map(vmap(aggregate))/mul", "aggregate"),
    ("jit(pack_x)/pack/concatenate", "pack"),
    ("jit(scan_x)/vmap()/lt", ""),
    ("jit(scan_x)/jit(_where)/select_n", ""),
    ("jit(_pad)", ""), ("", "")])
def test_scope_of_an_operation(tf_op, scope):
    assert xplane.scope_of(tf_op) == scope


def test_seconds_by_scope():
    assert xplane.seconds_by_scope(OPS) == {
        "filter": pytest.approx(0.016), "aggregate": pytest.approx(0.052),
        "": pytest.approx(0.001)}
    made = [("%while.1 = (u32[], s32[8]) while(...)", 0, 2 * MS, "")]
    assert xplane.seconds_outside_scopes(OPS + made) == {
        "jit(_pad)/pad:": pytest.approx(0.001),
        "%while.1": pytest.approx(0.002)}


# -- the file format: a small XSpace written by hand ---------------------------


def _varint(x):
    out = bytearray()
    while True:
        out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
        x >>= 7
        if not x:
            return bytes(out)


def _f(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(name, stat_names, metas, lines, stats=b""):
    body = _f(2, name) + stats
    for sid, sname in stat_names.items():
        body += _f(5, _f(1, sid) + _f(2, _f(1, sid) + _f(2, sname)))
    for mid, (mname, mstats) in metas.items():
        body += _f(4, _f(1, mid) + _f(2, _f(1, mid) + _f(2, mname) + mstats))
    for lid, lname, base_ns, events in lines:
        line = _f(1, lid) + _f(2, lname) + _f(3, base_ns)
        for mid, offset_ps, dur_ps, estats in events:
            line += _f(4, _f(1, mid) + _f(2, offset_ps) + _f(3, dur_ps)
                       + estats)
        body += _f(3, line)
    return _f(1, body)


def test_read_xplane_reads_annotations_and_the_scope_of_device_ops(tmp_path):
    host = _plane(
        "/host:CPU", {1: "query_id", 2: "span_id"},
        {1: ("family_dispatch", b""), 2: ("PjitFunction(scan_x)", b"")},
        [(77, "python", 1000,
          [(1, 5_000_000, 2_000_000,
            _f(4, _f(1, 1) + _f(5, "q7")) + _f(4, _f(1, 2) + _f(3, 12))),
           (2, 6_000_000, 500_000, b"")])])
    device = _plane(
        "/device:TPU:0", {1: "tf_op", 2: "flops"},
        {1: ("%fusion.3 = ...",
             _f(5, _f(1, 1) + _f(5, "jit(scan_x)/vmap(filter)/and:"))
             + _f(5, _f(1, 2) + _f(3, 64))),
         2: ("jit_scan_x(42)", b"")},
        [(1, "XLA Modules", 0, [(2, 9_000_000, 4_000_000, b"")]),
         (2, "XLA Ops", 0, [(1, 9_100_000, 3_000_000, b"")])])
    env = _plane("Task Environment", {1: "profile_start_time"}, {}, [],
                 stats=_f(6, _f(1, 1) + _f(3, 1_790_000_000_000_000_000)))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(host + device + env)
    trace = xplane.read_xplane(path)
    assert trace["start_ns"] == 1_790_000_000_000_000_000
    assert trace["stop_ns"] is None
    assert trace["host"] == {"python/77": [
        ("family_dispatch", 6000, 2000, {"query_id": "q7", "span_id": 12}),
        ("PjitFunction(scan_x)", 7000, 500, {})]}
    assert trace["modules"] == [("jit_scan_x(42)", 9000, 4000)]
    assert trace["ops"] == [("%fusion.3 = ...", 9100, 3000,
                             "jit(scan_x)/vmap(filter)/and:")]
    assert xplane.annotations(trace["host"]) == {
        "python/77": [("family_dispatch", 6000, 8000)]}


# -- the readers ---------------------------------------------------------------


def _reader(name):
    return module_at(HERE / "readers" / f"{name}.py")


def _record(start, end, trace):
    return types.SimpleNamespace(start=start, end=end, trace=trace)


def test_span_layer_reads_nothing_where_its_spans_are_missing():
    qp = {"operator": "QUERY_PROCESSING", "durationMs": 90.0}
    scatter = {"operator": "BROKER_SCATTER", "durationMs": 100.0}
    params = {"plus": ["BROKER_SCATTER"], "minus": ["QUERY_PROCESSING"],
              "needs": ["QUERY_PROCESSING"]}
    run = types.SimpleNamespace(records=[
        _record(0, 1, [scatter, qp]), _record(0, 1, [scatter, qp]),
        _record(0, 1, None)])
    assert _reader("span_layer").read(run, params) == pytest.approx(10.0)
    # the parent's trace: a scatter span, no server-side total
    run = types.SimpleNamespace(records=[_record(0, 1, [scatter])])
    assert _reader("span_layer").read(run, params) is None
    assert _reader("span_sum").read(run, params) == pytest.approx(100.0)


def test_span_attr_means_a_span_attribute_over_traced_requests():
    fetch = {"operator": "DEVICE_FETCH", "attributes": {"hostFetches": 1}}
    run = types.SimpleNamespace(records=[
        _record(0, 1, [fetch, fetch, {"operator": "SERVER_COMBINE"}]),
        _record(0, 1, [{"operator": "RESULT_CACHE(hit)"}]),
        _record(0, 1, None)])
    params = {"span": "DEVICE_FETCH", "attribute": "hostFetches"}
    assert _reader("span_attr").read(run, params) == pytest.approx(1.0)
    # the parent's trace has no such span: nothing, not 0
    run = types.SimpleNamespace(records=[
        _record(0, 1, [{"operator": "family_dispatch",
                        "attributes": {"deviceExecMs": 3.0}}])])
    assert _reader("span_attr").read(run, params) is None
    assert _reader("span_attr").read(
        types.SimpleNamespace(records=[_record(0, 1, None)]), params) is None


def test_trace_readers_on_the_hand_built_trace(monkeypatch):
    monkeypatch.setattr(xplane, "_TRACES", {"here": {
        "start_ns": 1_000, "stop_ns": 1_000 + 151 * MS, "host": HOST,
        "modules": MODULES, "ops": OPS + WARM_OP}})
    # a slice 0.0-0.2 s holding one whole request and half of another
    run = types.SimpleNamespace(
        trace={"busy_s": 0.074}, slice=(0.0, 0.2), trace_dir="here",
        records=[_record(0.0, 0.1, [1]), _record(0.15, 0.25, [1])])
    assert _reader("launch_to_start").read(
        run, {"span": "family_dispatch", "module_prefix": "jit_scan_"}) \
        == pytest.approx(13.0)
    assert _reader("idle_by_host").read(run, {}) \
        == pytest.approx(100 * 57 / 77)
    by_scope = _reader("device_by_scope")
    assert by_scope.read(run, {"scopes": ["filter"]}) \
        == pytest.approx(16.0 / 1.5)
    assert by_scope.read(run, {"scopes": ["aggregate", "filter"]}) \
        == pytest.approx(68.0 / 1.5)
    assert by_scope.read(run, {"scopes": ["group_by_dense"]}) is None
    # an untraced run reads nothing, whatever lies on the disk
    run.trace = None
    for name, params in (("launch_to_start", {"span": "family_dispatch",
                                              "module_prefix": "jit_scan_"}),
                         ("idle_by_host", {}),
                         ("device_by_scope", {"scopes": ["filter"]})):
        assert _reader(name).read(run, params) is None


def test_trace_readers_where_there_is_no_trace_file(tmp_path, monkeypatch):
    monkeypatch.setattr(xplane, "_TRACES", {})
    run = types.SimpleNamespace(trace={"busy_s": 1.0}, slice=(0.0, 1.0),
                                trace_dir=tmp_path,
                                records=[_record(0.0, 0.5, [1])])
    assert _reader("idle_by_host").read(run, {}) is None
    assert _reader("launch_to_start").read(
        run, {"span": "family_dispatch", "module_prefix": "jit_scan_"}) is None
    assert _reader("device_by_scope").read(run, {"scopes": ["filter"]}) is None


def test_trace_reads_the_runs_own_trace_dir_and_no_other(tmp_path,
                                                         monkeypatch):
    monkeypatch.setattr(xplane, "_TRACES", {})
    mine, other = tmp_path / "bench_trace_a", tmp_path / "bench_trace_b"
    mine.mkdir()
    d = other / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(_plane("/host:CPU", {}, {}, []))
    # a newer trace of another run beside it is not this run's
    assert xplane.trace(mine) is None
    assert xplane.trace(other) == {"start_ns": None, "stop_ns": None,
                                   "host": {}, "modules": [], "ops": []}
