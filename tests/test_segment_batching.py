"""Stacked segment batching (ISSUE 3): bit-parity oracle + structure guards.

Two families of checks:

  * PARITY — every cell of {COUNT, SUM, MIN, MAX, DISTINCTCOUNT, AVG} ×
    {filter, no filter} over MIXED segment sizes spanning a pad-bucket
    boundary (6000/9000/3000 rows straddle the 8192 bucket) must return
    rows bit-for-bit equal to `SET segmentBatch = false` (per-segment
    dispatch). Sparse group-by + device combine and plain selections ride
    the same oracle.

  * STRUCTURE — a multi-segment single-family query must execute with
    exactly ONE device dispatch (was S), the compile guard must record one
    family key (not S per-segment keys), mixed pad buckets must split into
    exactly the predicted number of families, and EXPLAIN IMPLEMENTATION
    must surface the SEGMENT_BATCH row.
"""

from __future__ import annotations

import numpy as np
import pytest

from pinot_tpu.engine import executor as executor_mod
from pinot_tpu.engine.query_executor import QueryExecutor
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema

SCHEMA = Schema.build(
    "sb",
    dimensions=[("k", "INT"), ("d", "INT")],
    metrics=[("v", "LONG"), ("f", "DOUBLE")])

N_KEYS = 40
# 6000/3000 pad to the 8192 bucket, 9000 pads to 16384 — the fixture
# deliberately straddles a bucket boundary so batching must mix stacked
# and differently-shaped segments in one query
MIXED_SIZES = [6000, 9000, 3000]

NO_BATCH = "SET segmentBatch = false; "


@pytest.fixture(autouse=True)
def _no_segment_cache(monkeypatch):
    # the segment partial-result cache (cache/partial.py) would satisfy
    # repeat queries with zero dispatches — and since segmentBatch is an
    # execution-only option, the NO_BATCH "solo" runs share the batched
    # runs' fingerprints and would hit their cached partials, turning every
    # parity oracle and dispatch-count guard here into a self-comparison.
    # This module tests the dispatcher, so caching is off throughout.
    monkeypatch.setenv("PINOT_TPU_SEGMENT_CACHE", "0")


def _gen(rng, n):
    return {
        "k": rng.integers(0, N_KEYS, n).astype(np.int32),
        "d": rng.integers(0, 16, n).astype(np.int32),
        # 1000 possible values keeps every segment's v-dictionary inside
        # the 1024 pad bucket regardless of segment size
        "v": rng.integers(-500, 500, n).astype(np.int64),
        "f": rng.normal(100.0, 25.0, n).astype(np.float64),
    }


@pytest.fixture(scope="module")
def mixed(tmp_path_factory):
    rng = np.random.default_rng(31)
    d = tmp_path_factory.mktemp("sb_mixed")
    segs = []
    for i, n in enumerate(MIXED_SIZES):
        SegmentBuilder(SCHEMA, segment_name=f"m{i}").build(
            _gen(rng, n), d / f"m{i}")
        segs.append(load_segment(d / f"m{i}"))
    qe = QueryExecutor(backend="tpu")
    qe.add_table(SCHEMA, segs)
    return qe


@pytest.fixture(scope="module")
def uniform(tmp_path_factory):
    """Four segments built from IDENTICAL rows: metadata (and therefore the
    batch family key) is equal by construction — one family, guaranteed."""
    rng = np.random.default_rng(77)
    cols = _gen(rng, 2048)
    d = tmp_path_factory.mktemp("sb_uniform")
    segs = []
    for i in range(4):
        SegmentBuilder(SCHEMA, segment_name=f"u{i}").build(cols, d / f"u{i}")
        segs.append(load_segment(d / f"u{i}"))
    qe = QueryExecutor(backend="tpu")
    qe.add_table(SCHEMA, segs)
    return qe


def _rows(resp):
    assert not resp.exceptions, resp.exceptions
    return resp.result_table.rows


def _assert_parity(qe, sql):
    batched = qe.execute_sql(sql)
    solo = qe.execute_sql(NO_BATCH + sql)
    # bit-for-bit: no tolerance, floats included — the batched kernel is a
    # vmap of the exact per-segment impl and combines in segment order
    assert _rows(batched) == _rows(solo), sql
    assert batched.num_docs_scanned == solo.num_docs_scanned
    return batched, solo


MATRIX_SQL = ("SELECT k, COUNT(*), SUM(v), MIN(v), MAX(v), "
              "DISTINCTCOUNT(d), AVG(v) FROM sb {where}"
              "GROUP BY k ORDER BY k LIMIT 100000")


@pytest.mark.parametrize("where", ["", "WHERE v > 100 AND d < 12 "],
                         ids=["nofilter", "filter"])
def test_groupby_matrix_parity(mixed, where):
    _assert_parity(mixed, MATRIX_SQL.format(where=where))


@pytest.mark.parametrize("where", ["", "WHERE v > 100 AND d < 12 "],
                         ids=["nofilter", "filter"])
def test_aggregation_only_parity(mixed, where):
    _assert_parity(
        mixed, "SELECT COUNT(*), SUM(v), MIN(v), MAX(v), "
               f"DISTINCTCOUNT(d), AVG(f), SUM(f) FROM sb {where}")


def test_sparse_groupby_device_combine_parity(mixed):
    # the batched RAW dispatch must feed the device-side sparse combine the
    # same per-segment tables, in the same merge order, as solo dispatch
    for where in ("", "WHERE v > 100 "):
        _assert_parity(
            mixed, "SET sparseGroupBy = true; "
                   "SELECT k, COUNT(*), SUM(v), DISTINCTCOUNT(d) FROM sb "
                   f"{where}GROUP BY k ORDER BY k LIMIT 100000")


def test_selection_parity(mixed):
    _assert_parity(
        mixed, "SELECT k, d, v FROM sb WHERE v > 250 LIMIT 50")


def test_double_sum_parity(mixed):
    _assert_parity(
        mixed, "SELECT k, SUM(f), AVG(f) FROM sb "
               "GROUP BY k ORDER BY k LIMIT 1000")


# -- structure guards --------------------------------------------------------

STRUCT_SQL = "SELECT k, SUM(v), COUNT(*) FROM sb GROUP BY k ORDER BY k LIMIT 1000"


def test_single_family_is_one_dispatch(uniform):
    batched = uniform.execute_sql(STRUCT_SQL)
    solo = uniform.execute_sql(NO_BATCH + STRUCT_SQL)
    assert _rows(batched) == _rows(solo)
    # the tentpole: 4 identical segments = 1 family = 1 device dispatch
    assert batched.num_device_dispatches == 1
    assert solo.num_device_dispatches == 4


def test_steady_state_has_zero_compiles(uniform):
    uniform.execute_sql(STRUCT_SQL)  # warm the compile guard
    again = uniform.execute_sql(STRUCT_SQL)
    assert not again.exceptions
    assert again.num_device_dispatches == 1
    assert again.num_compiles == 0


def test_compile_guard_records_one_family_not_s(uniform, monkeypatch):
    guard = executor_mod._CompileCacheGuard()
    monkeypatch.setattr(executor_mod, "_GUARD", guard)
    resp = uniform.execute_sql(STRUCT_SQL)
    assert not resp.exceptions
    # one guard entry for the whole 4-segment query — the batched key, with
    # the batch size as its trailing component — NOT one entry per segment
    assert len(guard._seen) == 1
    (key,) = guard._seen
    assert key[0] == "batch"
    assert key[-1] == 4


def test_mixed_buckets_split_into_two_families(mixed):
    batched = mixed.execute_sql(STRUCT_SQL)
    solo = mixed.execute_sql(NO_BATCH + STRUCT_SQL)
    assert _rows(batched) == _rows(solo)
    # 6000+3000 share the 8192 pad bucket; 9000 pads to 16384: 2 families
    assert batched.num_device_dispatches == 2
    assert solo.num_device_dispatches == 3


def test_explain_implementation_shows_segment_batch(uniform):
    r = uniform.execute_sql("EXPLAIN IMPLEMENTATION FOR " + STRUCT_SQL)
    ops = [row[0] for row in _rows(r)]
    assert any(op == "SEGMENT_BATCH(families:1, segments:4)" for op in ops), ops
    r2 = uniform.execute_sql(
        NO_BATCH + "EXPLAIN IMPLEMENTATION FOR " + STRUCT_SQL)
    ops2 = [row[0] for row in _rows(r2)]
    assert any(op == "SEGMENT_BATCH(disabled)" for op in ops2), ops2


def test_counters_surface_in_json(uniform):
    r = uniform.execute_sql(STRUCT_SQL)
    j = r.to_json()
    assert j["numDeviceDispatches"] == 1
    assert "numCompiles" in j


# -- cache/OOM regression guards ---------------------------------------------


class _FakeSeg:
    num_docs = 100


class _FakeSnap:
    num_docs = 100
    is_mutable = True


def test_stacked_view_survives_budget_pressure():
    # regression: with segment views alone over budget, registering a new
    # stack used to drain _stack_order (the fresh 0-byte stack included)
    # and then KeyError on the return read
    from pinot_tpu.segment.device_cache import DeviceSegmentCache

    cache = DeviceSegmentCache(budget_bytes=16)
    s1, s2 = _FakeSeg(), _FakeSeg()
    v1 = cache.view(s1)
    v1._planes[("c", "ids")] = np.zeros(64, np.int32)  # 256 bytes > budget
    sv = cache.stacked_view([s1, s2])
    # the just-registered stack must survive the same-call eviction pass
    assert cache.stacked_view([s1, s2]) is sv


def test_snapshot_members_skip_stack_cache():
    # stacks are keyed by member id(); realtime snapshot views are fresh
    # objects per query, so caching them would only pin dead HBM bytes
    from pinot_tpu.segment.device_cache import DeviceSegmentCache

    cache = DeviceSegmentCache()
    imm, snap = _FakeSeg(), _FakeSnap()
    sv1 = cache.stacked_view([imm, snap])
    sv2 = cache.stacked_view([imm, snap])
    assert sv1 is not sv2
    assert not cache._stacks and not cache._stack_order


def test_batched_oom_falls_back_to_per_segment(uniform, monkeypatch):
    # a family near HBM capacity can OOM batched (2x footprint) yet fit
    # per-segment — the dispatcher must fall back, not fail the query
    def boom(*a, **k):
        raise MemoryError("fake HBM OOM")

    monkeypatch.setattr(uniform.tpu, "dispatch_plan_batch", boom)
    resp = uniform.execute_sql(STRUCT_SQL)
    assert _rows(resp) == _rows(uniform.execute_sql(NO_BATCH + STRUCT_SQL))
    assert resp.num_device_dispatches == 4  # per-segment path ran


def test_sparse_combine_batched_oom_falls_back(mixed, monkeypatch):
    def boom(*a, **k):
        raise MemoryError("fake HBM OOM")

    monkeypatch.setattr(mixed.tpu, "dispatch_plan_batch_raw", boom)
    _assert_parity(
        mixed, "SET sparseGroupBy = true; "
               "SELECT k, COUNT(*), SUM(v) FROM sb "
               "GROUP BY k ORDER BY k LIMIT 100000")


# -- one plan a segment, one family_dispatch span whatever the entry ---------

WIDE_KEYS = 1 << 15  # the planner sorts a table from this many keys up
WIDE = Schema.build("sbw", dimensions=[("wk", "INT")], metrics=[("wv", "INT")])


@pytest.fixture(scope="module")
def sixteen(tmp_path_factory):
    """Sixteen segments of `sb` (40 keys: dense tables) and sixteen of `sbw`
    (a key of its own in every row, 32,768 a segment: the planner sorts the
    table by its own rule and the server merges on the device)."""
    rng = np.random.default_rng(53)
    d = tmp_path_factory.mktemp("sb_sixteen")
    small, wide = [], []
    for i in range(16):
        SegmentBuilder(SCHEMA, segment_name=f"x{i}").build(
            _gen(rng, 1024), d / f"x{i}")
        small.append(load_segment(d / f"x{i}"))
        SegmentBuilder(WIDE, segment_name=f"w{i}").build(
            {"wk": rng.permutation(WIDE_KEYS).astype(np.int32) + 7 * i,
             "wv": rng.integers(0, 100, WIDE_KEYS).astype(np.int32)},
            d / f"w{i}")
        wide.append(load_segment(d / f"w{i}"))
    qe = QueryExecutor(backend="tpu")
    qe.add_table(SCHEMA, small)
    qe.add_table(WIDE, wide)
    return qe


@pytest.mark.parametrize("sql,mode,merged", [
    pytest.param("SELECT k, SUM(v) FROM sb GROUP BY k LIMIT 100",
                 "group_by", False, id="dense"),
    pytest.param("SELECT wk, SUM(wv) FROM sbw GROUP BY wk "
                 "ORDER BY SUM(wv) DESC, wk LIMIT 10",
                 "group_by_sparse", True, id="sorted-top-n"),
    # a sparse group-by the device merge declines (DISTINCTCOUNT is no
    # column it merges): the per-segment stages take the plans it was shown
    pytest.param("SET sparseGroupBy = true; SELECT k, DISTINCTCOUNT(d) "
                 "FROM sb GROUP BY k ORDER BY k LIMIT 100",
                 "group_by_sparse", False, id="sparse-declined"),
])
def test_each_kept_segment_is_routed_and_planned_once(sixteen, monkeypatch,
                                                      sql, mode, merged):
    """One route and one plan a kept segment, whichever way the query goes
    (before PR 31: 17, 16 and 32 plans for these three)."""
    from pinot_tpu.engine.plan import SegmentPlanner

    planned, modes = [], set()
    real = SegmentPlanner.plan

    def counting(self):
        planned.append(self.segment.name)
        plan = real(self)
        modes.add(plan.program.mode)
        return plan

    monkeypatch.setattr(SegmentPlanner, "plan", counting)
    routed = []
    real_route = sixteen._segment_route

    def counting_route(query, segment):
        routed.append(segment.name)
        return real_route(query, segment)

    monkeypatch.setattr(sixteen, "_segment_route", counting_route)
    resp = sixteen.execute_sql("SET trace = true; " + sql)
    assert _rows(resp)
    assert modes == {mode}
    names = sorted(s.name for s in sixteen.tables["sbw" if "sbw" in sql else "sb"].segments)
    assert sorted(planned) == names and sorted(routed) == names, \
        f"{len(planned)} plans and {len(routed)} routes for 16 segments"
    assert resp.num_device_dispatches == 1
    combine = [s for s in resp.trace_info
               if s["operator"] == "SERVER_COMBINE"]
    assert ("groupsCombined" in combine[0]["attributes"]) == merged


@pytest.mark.parametrize("entry,members,aggs", [
    ("dispatch_plan", 1, "MIN(v), MAX(f)"),
    ("dispatch_plan_raw", 1, "MAX(v), MIN(f)"),
    ("dispatch_plan_batch", 4, "SUM(f), MIN(v)"),
    ("dispatch_plan_batch_raw", 4, "SUM(f), MAX(v)")])
def test_every_dispatch_entry_leaves_one_family_dispatch_span(
        uniform, entry, members, aggs):
    """The four public entries share one wrapper and one launch routine:
    each leaves ONE family_dispatch span that names the program, the
    segments, the compile and the transfers, and counts one dispatch."""
    from pinot_tpu.engine.ir import program_label
    from pinot_tpu.engine.query_executor import parse_sql
    from pinot_tpu.ops.kernels import PackedOuts
    from pinot_tpu.segment.device_cache import pad_bucket
    from pinot_tpu.spi.trace import TRACING

    # aggregations of its own for every entry: each compiles its program
    sql = f"SELECT k, {aggs} FROM sb WHERE d < 9 GROUP BY k LIMIT 100"
    segs = list(uniform.tables["sb"].segments)[:members]
    plans = [uniform.tpu.plan(parse_sql(sql), s) for s in segs]
    args = (segs[0], plans[0]) if members == 1 else (segs, plans)
    spans = []
    for _ in range(2):
        executor_mod.reset_dispatch_counters()
        trace = TRACING.start_trace(f"entry:{entry}")
        try:
            out = getattr(uniform.tpu, entry)(*args)
        finally:
            TRACING.end_trace()
        assert executor_mod.dispatch_counters()[0] == 1
        # packed for the host, or (raw outputs, view or views) for a
        # caller that stays on the device
        assert isinstance(out, PackedOuts) != entry.endswith("_raw")
        fam = [s for s in trace.to_json()
               if s["operator"] == "family_dispatch"]
        assert len(fam) == 1
        spans.append(fam[0]["attributes"])
    first, again = spans
    assert first["program"] == program_label(plans[0].program)
    assert first["mode"] == "group_by"
    assert first["padded"] == pad_bucket(segs[0].num_docs)
    assert first["numSegments"] == members
    assert first.get("segment") == (segs[0].name if members == 1 else None)
    assert first["compileMs"] > 0 and again["compileMs"] == 0.0
    for attrs in spans:
        assert {"transferBytes", "stackHits", "stackMisses", "dictLookups",
                "hbmBytesUsed", "hbmBudgetBytes",
                "hbmEvictions"} <= set(attrs)
    gathers = [s for s in trace.to_json() if s["operator"] == "GATHER_STACK"]
    assert len(gathers) == (members > 1)
