"""One span tree per request that names every wait, on the profiler's
clock, with stable device names.

- the tree of a traced query on a two-server cluster: every span of the
  server-side tree once per shard, children inside their parent, `startNs`
  inside the client's wall and ordered broker -> server -> broker;
- under a profiler session the spans are TraceAnnotations on its host
  plane, with the spans' names, ids and clock;
- the program label: equal for two literals of one SQL shape, different
  for another shape, the name of the lowered module and of its ops' scopes;
- every `family_dispatch` span says how many slots a member's group table has
  (`groupSlots`; 0 for a program with none).
"""

from __future__ import annotations

import re
import tempfile
import time
from pathlib import Path

import numpy as np
import pytest

from pinot_tpu.cluster import (Broker, ClusterController, PropertyStore,
                               ServerInstance)
from pinot_tpu.engine.ir import program_label
from pinot_tpu.engine.plan import table_bucket
from pinot_tpu.engine.query_executor import QueryExecutor, parse_sql
from pinot_tpu.query.optimizer import optimize_filter
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema

ST = Schema.build("sptab", dimensions=[("spk", "INT"), ("spy", "INT")],
                  metrics=[("spv", "INT"), ("spd", "INT")])
NOCACHE = "SET resultCache = false; SET segmentCache = false; "
SQL = "SELECT spk, SUM(spv) FROM sptab WHERE spy = 3 GROUP BY spk LIMIT 50"
SERVER_TREE = ("QUERY_PROCESSING", "SCHEDULER_WAIT", "BUILD_QUERY_PLAN",
               "family_dispatch", "GATHER_STACK", "DEVICE_FETCH",
               "SERVER_COMBINE", "RESPONSE_SERIALIZATION")
SLACK_MS = 1.0  # two traces read the epoch clock microseconds apart


def _segments(d: Path, n: int) -> list:
    rng = np.random.default_rng(5)
    paths = []
    for i in range(n):
        cols = {"spk": rng.integers(0, 16, 600).astype(np.int32),
                "spy": rng.integers(0, 7, 600).astype(np.int32),
                "spv": rng.integers(0, 100, 600).astype(np.int32),
                "spd": rng.integers(0, 11, 600).astype(np.int32)}
        SegmentBuilder(ST, segment_name=f"sptab_{i}").build(
            cols, d / f"sptab_{i}")
        paths.append(d / f"sptab_{i}")
    return paths


@pytest.fixture(scope="module")
def cluster():
    d = Path(tempfile.mkdtemp(prefix="sptree_"))
    store = PropertyStore()
    controller = ClusterController(store)
    servers = [ServerInstance(store, f"Server_{i}", backend="tpu")
               for i in range(2)]
    for s in servers:
        s.start()
    controller.add_schema(ST.to_json())
    t = controller.create_table({"tableName": "sptab", "replication": 1})
    for i, path in enumerate(_segments(d, 8)):
        controller.add_segment(t, f"sptab_{i}", {"location": str(path),
                                                 "numDocs": 600})
    broker = Broker(store)
    warm = broker.execute_sql(NOCACHE + SQL)
    assert not warm.exceptions, warm.exceptions
    yield broker
    for s in servers:
        s.stop()


def _end(span) -> float:
    return span["startNs"] / 1e6 + span["durationMs"]


def test_span_tree_of_a_traced_query_on_two_servers(cluster):
    wall0 = time.time_ns()
    resp = cluster.execute_sql("SET trace = true; " + NOCACHE + SQL)
    wall1 = time.time_ns()
    assert not resp.exceptions, resp.exceptions
    assert resp.num_servers_queried == 2
    spans = resp.trace_info
    by_id = {s["spanId"]: s for s in spans}
    assert len(by_id) == len(spans)
    broker = [s for s in spans if not isinstance(s["spanId"], str)]
    scatter = next(s for s in broker if s["operator"] == "BROKER_SCATTER")
    reduce_ = next(s for s in broker if s["operator"] == "BROKER_REDUCE")
    shards = {}
    for s in spans:
        if isinstance(s["spanId"], str):
            shards.setdefault(s["spanId"].rsplit(":", 1)[0], []).append(s)
    assert sorted(shards) == ["Server_0", "Server_1"]
    for shard, own in shards.items():
        names = [s["operator"] for s in own]
        for name in SERVER_TREE:
            assert names.count(name) == 1, (shard, name, names)
        root = next(s for s in own if s["operator"] == "QUERY_PROCESSING")
        assert "parentId" not in root and root["server"] == shard
        assert root["attributes"]["queryId"] == resp.query_id
        by_name = {s["operator"]: s for s in own}
        for child in SERVER_TREE[1:]:
            parent = "family_dispatch" if child == "GATHER_STACK" \
                else "QUERY_PROCESSING"
            assert by_id[by_name[child]["parentId"]]["operator"] == parent
        # every child lies inside its parent, and siblings sum to no more
        for s in own:
            if "parentId" not in s:
                continue
            parent = by_id[s["parentId"]]
            assert s["startNs"] >= parent["startNs"]
            assert _end(s) <= _end(parent) + 0.01
        for parent in own:
            kids = [s for s in own if s.get("parentId") == parent["spanId"]]
            assert sum(k["durationMs"] for k in kids) \
                <= parent["durationMs"] + 0.01 * len(kids)
        # no sync under the dispatch: the wait has a span of its own
        assert "deviceExecMs" not in by_name["family_dispatch"]["attributes"]
        assert by_name["family_dispatch"]["attributes"]["program"] \
            == "gby_rng_i0_by1_sum_d2"
        assert by_name["DEVICE_FETCH"]["attributes"]["hostFetches"] == 1
        # one clock: broker -> server -> broker
        assert scatter["startNs"] / 1e6 <= root["startNs"] / 1e6 + SLACK_MS
        assert _end(root) <= _end(scatter) + SLACK_MS
        assert _end(root) <= reduce_["startNs"] / 1e6 + SLACK_MS
    for s in broker:
        assert s["attributes"]["queryId"] == resp.query_id
    for s in spans:
        assert wall0 - SLACK_MS * 1e6 <= s["startNs"]
        assert _end(s) * 1e6 <= wall1 + SLACK_MS * 1e6
    # the layers add up to the whole, no span counted twice, none missing
    for shard, own in shards.items():
        by_name = {s["operator"]: s for s in own}
        covered = sum(by_name[n]["durationMs"] for n in (
            "SCHEDULER_WAIT", "BUILD_QUERY_PLAN", "family_dispatch",
            "DEVICE_FETCH", "SERVER_COMBINE", "RESPONSE_SERIALIZATION"))
        assert covered <= by_name["QUERY_PROCESSING"]["durationMs"] + 0.05


def test_traced_repeat_hits_the_segment_cache_and_says_so(cluster):
    sql = "SET resultCache = false; " + SQL.replace("spy = 3", "spy = 4")
    first = cluster.execute_sql(sql)
    assert not first.exceptions and first.num_device_dispatches == 2
    traced = cluster.execute_sql("SET trace = true; " + sql)
    assert not traced.exceptions
    assert traced.num_device_dispatches == 0
    assert traced.num_segments_cache_hit == 8
    assert traced.result_table.rows == first.result_table.rows
    ops = [s["operator"] for s in traced.trace_info]
    assert ops.count("SEGMENT_CACHE(hit)") == 2
    assert "family_dispatch" not in ops and "DEVICE_FETCH" not in ops
    hit = next(s for s in traced.trace_info
               if s["operator"] == "SEGMENT_CACHE(hit)")
    assert hit["attributes"] == {"segments": 4, "cache": "hit"}


# -- the profiler's clock ------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    d = Path(tempfile.mkdtemp(prefix="sptree_qe_"))
    qe = QueryExecutor(backend="tpu")
    qe.add_table(ST, [load_segment(p) for p in _segments(d, 4)])
    r = qe.execute_sql(NOCACHE + SQL)
    assert not r.exceptions, r.exceptions
    return qe


def test_spans_are_annotations_on_the_profilers_host_plane(engine, tmp_path):
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        resp = engine.execute_sql("SET trace = true; " + NOCACHE + SQL)
    finally:
        jax.profiler.stop_trace()
    assert not resp.exceptions, resp.exceptions
    profile = ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb"))))
    start_ns, found = None, {}
    for plane in profile.planes:
        stats = dict(plane.stats)
        start_ns = stats.get("profile_start_time", start_ns)
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                st = dict(ev.stats)
                if "span_id" in st:
                    found[st["span_id"]] = (ev.name, st["query_id"],
                                            ev.start_ns, ev.duration_ns)
    spans = {s["spanId"]: s for s in resp.trace_info}
    assert set(found) == set(spans)
    root = next(s for s in resp.trace_info if "parentId" not in s)
    for span_id, (name, query_id, rel_ns, dur_ns) in found.items():
        span = spans[span_id]
        assert name == span["operator"]
        assert query_id == root["attributes"]["queryId"]
        assert dur_ns / 1e6 == pytest.approx(span["durationMs"], abs=1.0)
        # the profiler stamps events with the epoch clock, as `startNs` is
        assert start_ns is not None
        assert abs(start_ns + rel_ns - span["startNs"]) < 2e6, name
    assert {"family_dispatch", "GATHER_STACK", "DEVICE_FETCH",
            "BUILD_QUERY_PLAN"} <= {v[0] for v in found.values()}


# -- stable names on the device -------------------------------------------------


def _plan(engine, sql):
    query = parse_sql(sql)
    query.filter = optimize_filter(query.filter)
    segment = engine.tables["sptab"].segments[0]
    return segment, engine.tpu.plan(query, segment)


Q11 = ("SELECT SUM(spv * spd) FROM sptab WHERE spy = {y} AND spd BETWEEN {d} "
       "AND {d2} AND spv < {q}")
Q21 = "SELECT SUM(spv), spy, spk FROM sptab WHERE spd = {d} GROUP BY spy, spk"


def test_program_label_is_the_shape_not_the_literals(engine):
    _, a = _plan(engine, Q11.format(y=3, d=1, d2=3, q=25))
    _, b = _plan(engine, Q11.format(y=5, d=4, d2=6, q=24))
    _, c = _plan(engine, Q21.format(d=2))
    assert program_label(a.program) == program_label(b.program) \
        == "agg_and3_rng_i0_rng_i1_rng_i2_sum_mul_d2_d1"
    assert program_label(c.program) == "gby_rng_i0_by1x2_sum_d3"
    assert re.fullmatch(r"[a-z0-9_]+", program_label(c.program))


def test_label_names_the_module_and_scopes_name_its_ops(engine):
    from pinot_tpu.ops import kernels

    segment, plan = _plan(engine, Q21.format(d=2))
    view = engine.tpu.cache.view(segment)
    arrays, packed = plan.gather_arrays_packed(view)
    params = tuple(np.asarray(p) for p in plan.params)
    label = program_label(plan.program)
    lowered = kernels.run_program.lower(
        plan.program, arrays, params, np.int32(segment.num_docs),
        view.padded, packed=packed)
    assert f"module @jit_scan_{label} " in lowered.as_text()
    names = set(re.findall(r'op_name="([^"]+)"',
                           lowered.compile().as_text()))
    scopes = {n.split("/")[1] for n in names
              if n.startswith(f"jit(scan_{label})/") and n.count("/") >= 2}
    assert {"filter", "group_by_dense"} <= scopes
    # the batched program and the pack carry the same label
    segs = engine.tables["sptab"].segments
    plans = [_plan(engine, Q21.format(d=2))[1] for _ in segs]
    views, arrays_b, params_b, packed_b, num_docs = \
        engine.tpu._gather_batch(list(segs), plans)
    batched = kernels.run_program_batch.lower(
        plan.program, arrays_b, params_b, num_docs, views[0].padded,
        packed=packed_b)
    assert f"module @jit_scan_{label} " in batched.as_text()
    assert "vmap(filter)" in batched.compile().as_text()
    outs = kernels.run_program(plan.program, arrays, params,
                               np.int32(segment.num_docs), view.padded,
                               packed=packed)
    pack = kernels.jit_named(kernels._pack_flat_impl, f"pack_{label}")
    text = pack.lower(outs).as_text()
    assert f"module @jit_pack_{label} " in text
    assert kernels.unpack_outputs(kernels.pack_outputs(outs, label))[0].sum() \
        == np.asarray(outs[0]).sum()


def _benchmark_scope_of():
    """`benchmark/xplane.scope_of`, the rule the per-layer kernel metrics
    file a device operation by (stdlib only; loaded by path)."""
    import importlib.util

    path = Path(__file__).resolve().parents[1] / "benchmark" / "xplane.py"
    spec = importlib.util.spec_from_file_location("bench_xplane", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.scope_of


# three keys whose product, 40 x 40 x 30 = 48,000, lies above the limb
# kernel's slots: the planner sorts the table by its own rule (PR 35), as
# it does `ssb16.flight3city`'s `c_city x s_city x d_year`
ST3 = Schema.build("sp3", dimensions=[("a", "STRING"), ("b", "STRING"),
                                      ("c", "INT"), ("e", "INT")],
                   metrics=[("spv", "INT")])
SQL3 = ("SELECT a, b, c, SUM(spv) FROM sp3 WHERE a IN ('a3', 'a17') AND "
        "b IN ('b5', 'b21') AND e < 6 GROUP BY a, b, c LIMIT 50")


@pytest.fixture(scope="module")
def engine3():
    d = Path(tempfile.mkdtemp(prefix="sptree_3k_"))
    rng = np.random.default_rng(11)
    segs = []
    for i in range(4):
        n = 2400
        k = np.arange(n)
        cols = {"a": np.char.add("a", (k % 40).astype(str)).astype(object),
                "b": np.char.add("b", (k // 40 % 40).astype(str)).astype(
                    object),
                "c": (k % 30).astype(np.int32),
                "e": (k % 8).astype(np.int32),
                "spv": rng.integers(0, 100, n).astype(np.int32)}
        SegmentBuilder(ST3, segment_name=f"sp3_{i}").build(cols, d / f"sp3_{i}")
        segs.append(load_segment(d / f"sp3_{i}"))
    qe = QueryExecutor(backend="tpu")
    qe.add_table(ST3, segs)
    return qe


def _three_key_family(engine3):
    from pinot_tpu.ops import kernels

    segs = list(engine3.tables["sp3"].segments)
    query = parse_sql(SQL3)
    query.filter = optimize_filter(query.filter)
    plans = [engine3.tpu.plan(query, seg) for seg in segs]
    program = plans[0].program
    assert program.mode == "group_by_sparse"  # no SET: the rule's own
    assert kernels.group_table_form(program) == "sorted"
    assert "lut0_lut1" in program_label(program)
    views, arrays, params, packed, num_docs = engine3.tpu._gather_batch(
        segs, plans)
    return kernels.run_program_batch.lower(
        program, arrays, params, num_docs, views[0].padded, packed=packed)


def _sorted_family(engine):
    from pinot_tpu.ops import kernels

    segs = list(engine.tables["sptab"].segments)
    sql = "SET sparseGroupBy = true; " \
        "SELECT spk, SUM(spv) FROM sptab WHERE spy < 5 GROUP BY spk LIMIT 50"
    plans = [_plan(engine, sql)[1] for _ in segs]
    assert plans[0].program.mode == "group_by_sparse"
    views, arrays, params, packed, num_docs = engine.tpu._gather_batch(
        segs, plans)
    return kernels.run_program_batch.lower(
        plans[0].program, arrays, params, num_docs, views[0].padded,
        packed=packed)


def _cut_merge(engine):
    import jax

    from pinot_tpu.ops import kernels

    s, k = 4, 13 << 13  # above kernels.QUARTER_MERGE_ABOVE_SLOTS: a branch
    tables = ((jax.ShapeDtypeStruct((s, k), np.int64),
               jax.ShapeDtypeStruct((s,), np.int64),
               jax.ShapeDtypeStruct((s, k + 1), np.int64),
               (jax.ShapeDtypeStruct((s, k + 1), np.float64),)),)
    return kernels.merge_group_tables.lower(
        tables, np.int64(5), np.int64(10), how=("base",), key32=True,
        kinds=("add",), order=(1, True, False), cut_slots=8, table_slots=0)


def test_dense_min_max_reductions_are_filed_under_group_by_dense(engine):
    # the masked reductions of a small table's MIN and MAX (PR 32) carry
    # the scope `_run_masked` opens, so `kernel_groupby_ms` counts them
    # (the scatters they replace ran as loops the trace gave no name)
    from pinot_tpu.ops import kernels

    segs = list(engine.tables["sptab"].segments)
    sql = "SELECT spy, MIN(spv), MAX(spv) FROM sptab WHERE spd < 9 " \
        "GROUP BY spy LIMIT 50"
    plans = [_plan(engine, sql)[1] for _ in segs]
    program = plans[0].program
    assert kernels.min_max_forms(program) == "reduce:2,scatter:0"
    views, arrays, params, packed, num_docs = engine.tpu._gather_batch(
        segs, plans)
    text = kernels.run_program_batch.lower(
        program, arrays, params, num_docs, views[0].padded,
        packed=packed).compile().as_text()
    scope_of = _benchmark_scope_of()
    names = set(re.findall(r'op_name="([^"]+)"', text))
    reductions = [n for n in names
                  if n.endswith(("/reduce_min", "/reduce_max"))]
    assert {n.rsplit("/", 1)[1] for n in reductions} \
        == {"reduce_min", "reduce_max"}
    assert all(scope_of(n) == "group_by_dense" for n in reductions), \
        reductions


@pytest.mark.parametrize("lower,table,scope,inside", [
    pytest.param(_sorted_family, "engine", "group_by_sparse", "while/body",
                 id="the-sorted-family-under-lax-map"),
    pytest.param(_cut_merge, "engine", "combine", "cond/branch",
                 id="the-merge-under-its-branch"),
    pytest.param(_three_key_family, "engine3", "group_by_sparse",
                 "while/body", id="the-three-key-family-under-lax-map"),
])
def test_ops_inside_a_loop_or_a_branch_are_filed_under_the_programs_scope(
        request, lower, table, scope, inside):
    engine = request.getfixturevalue(table)
    # a trace files an op under the FIRST part of its name after the
    # module's: a scope opened inside `lax.map` or `lax.cond` would read
    # `while` or `cond`, and `kernel_groupby_ms` would lose the scan
    scope_of = _benchmark_scope_of()
    names = set(re.findall(r'op_name="([^"]+)"',
                           lower(engine).compile().as_text()))
    # (the CPU's compiler leaves a few names of a called computation
    # without the module's part; the chip's does not)
    filed = {n: scope_of(n) for n in names if n.startswith("jit(")}
    assert not [n for n, sc in filed.items() if sc in ("while", "cond")]
    sorts = [n for n in names if n.endswith("/sort")]
    assert sorts and all(filed[n] == scope for n in sorts), sorts
    assert any(inside in n for n in sorts)
    if lower is _three_key_family:
        # its two LUT filters are gathers batched BEFORE the loop, under
        # `filter` (`kernel_filter_ms` reads them), and the composite key's
        # multiply-adds lie inside it, under the group-by's scope
        gathers = [n for n in names if n.endswith("/gather")]
        assert gathers and all(filed[n] == "filter" and "while" not in n
                               for n in gathers), gathers
        muls = [n for n in names if n.endswith("/mul")]
        assert muls and all(filed[n] == scope for n in muls), muls


# -- the slots of a dispatch's group table --------------------------------------


@pytest.mark.parametrize("sql,slots,table", [
    pytest.param(Q11.format(y=3, d=1, d2=3, q=25), 0, "none", id="ungrouped"),
    pytest.param("SELECT spk, spv FROM sptab WHERE spy = 3 LIMIT 5", 0,
                 "none", id="selection"),
    pytest.param(SQL, 16, "limb", id="one-key"),
    pytest.param("SELECT spk, spy, spd, SUM(spv) FROM sptab WHERE spv < 90 "
                 "GROUP BY spk, spy, spd LIMIT 2000", 16 * 7 * 11, "limb",
                 id="dense-three-keys"),
    pytest.param("SET sparseGroupBy = true; SELECT spk, SUM(spv) FROM sptab "
                 "WHERE spy < 5 GROUP BY spk LIMIT 50", None, "sorted",
                 id="sorted"),
    pytest.param(SQL3, table_bucket(40 * 40 * 30), "sorted",
                 id="three-keys-sorted-by-the-rule"),
    pytest.param("SELECT a, b, c, DISTINCTCOUNT(e) FROM sp3 WHERE e < 6 "
                 "GROUP BY a, b, c LIMIT 50", 40 * 40 * 30, "dense",
                 id="three-keys-a-bitmap-stays-dense"),
])
def test_dispatch_span_carries_the_slots_of_the_group_table(request, sql,
                                                            slots, table):
    # `groupSlots` (PR 34): what `group_slots_per_query` reads. A dense
    # table has the product of its keys' cardinalities, from the plan's
    # static shape; a sort-based one at most its numGroupsLimit, and by the
    # planner's rule a slot for every key. `groupTable` (PR 35) says which
    # table it is (`kernels.group_table_form`); no metric reads it
    engine = request.getfixturevalue(
        "engine3" if " sp3 " in sql else "engine")
    resp = engine.execute_sql("SET trace = true; " + NOCACHE + sql)
    assert not resp.exceptions, resp.exceptions
    spans = [s["attributes"] for s in resp.trace_info
             if s["operator"] == "family_dispatch"]
    assert spans and resp.num_device_dispatches == len(spans)
    for attrs in spans:
        assert isinstance(attrs["groupSlots"], int)
        assert attrs["groupTable"] == table
        if slots is None:
            assert attrs["mode"] == "group_by_sparse"
            assert attrs["groupSlots"] > 0
        else:
            assert attrs["groupSlots"] == slots


def test_group_table_form_names_a_presorted_table(engine):
    import dataclasses

    from pinot_tpu.ops import kernels

    _, plan = _plan(engine, "SET sparseGroupBy = true; " + SQL)
    assert kernels.group_table_form(plan.program) == "sorted"
    assert kernels.group_table_form(dataclasses.replace(
        plan.program, keys_presorted=True)) == "presorted"
