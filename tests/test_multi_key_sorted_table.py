"""A group-by of several dictionary keys whose table lies above the limb
kernel's slots (`engine/plan.SegmentPlanner._sorted_table_rule`, PR 35):
the shape of SSB's Q3.3, `c_city x s_city x d_year`, at toy scale.

Three keys of which two are strings, IN-list filters, 16 small segments
whose dictionaries differ (a city missing in one, a year in another):
the served answer equals the host engine's and sqlite's, row for row, and
every segment's sorted table equals the dense table of the same plan
re-sized (as `tools/groupby_crossover_sweep.py` builds its forms; the
program has no option for it).
"""

from __future__ import annotations

import dataclasses
import sqlite3

import numpy as np
import pytest

from pinot_tpu.engine.ir import program_label
from pinot_tpu.engine.plan import SegmentPlanner, table_bucket
from pinot_tpu.engine.query_executor import QueryExecutor
from pinot_tpu.ops import kernels, mxu_groupby
from pinot_tpu.query.optimizer import optimize_filter
from pinot_tpu.query.parser.sql import parse_sql
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.device_cache import SegmentDeviceView
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema
from pinot_tpu.spi.table_config import IndexingConfig, TableConfig

SEGMENTS, ROWS = 16, 8000
CITIES = [f"NATION{c // 10:02d}{c % 10}" for c in range(40)]
YEARS = 25  # 40 x 40 x 25 = 40,000 slots: above the limb kernel's 32,768
SCHEMA = Schema.build(
    "mkt", dimensions=[("c_city", "STRING"), ("s_city", "STRING"),
                       ("d_year", "INT")],
    metrics=[("rev", "INT")])
CONFIG = TableConfig(table_name="mkt", indexing=IndexingConfig(
    no_dictionary_columns=["rev"]))
NOCACHE = "SET resultCache = false; SET segmentCache = false; "
WHERE = ("WHERE c_city IN ('NATION007', 'NATION011', 'NATION025', "
         "'NATION039') AND s_city IN ('NATION000', 'NATION007', 'NATION018') "
         "AND d_year BETWEEN 1992 AND 2010 ")
SQL = ("SELECT c_city, s_city, d_year, SUM(rev), COUNT(*), MIN(rev), "
       "MAX(rev) FROM mkt " + WHERE + "GROUP BY c_city, s_city, d_year "
       "ORDER BY d_year ASC, SUM(rev) DESC, c_city, s_city LIMIT 1000")


def _columns(seg: int) -> dict:
    rng = np.random.default_rng(100 + seg)
    i = np.arange(ROWS)
    cols = {"c_city": np.asarray(CITIES, dtype=object)[i % 40],
            "s_city": np.asarray(CITIES, dtype=object)[(i // 40) % 40],
            "d_year": (1990 + (i * 7 + i // 1600 * 3 + seg) % YEARS).astype(
                np.int32),
            "rev": rng.integers(-5_000, 600_000, ROWS).astype(np.int32)}
    # every segment has the column's whole range: an aggregation's bounds
    # are static in the Program, and segments that share them share a family
    cols["rev"][:2] = -5_000, 599_999
    keep = np.ones(ROWS, dtype=bool)
    if seg == 5:  # a filtered city this segment has never seen
        keep &= cols["c_city"] != "NATION007"
    if seg == 9:  # a year of the range likewise
        keep &= cols["d_year"] != 1995
    return {c: a[keep] for c, a in cols.items()}


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    d = tmp_path_factory.mktemp("mkt")
    conn = sqlite3.connect(":memory:")
    conn.execute("CREATE TABLE mkt (c_city TEXT, s_city TEXT, d_year INT, "
                 "rev INT)")
    segs = []
    for seg in range(SEGMENTS):
        cols = _columns(seg)
        SegmentBuilder(SCHEMA, CONFIG, f"mkt_{seg}").build(
            cols, d / f"mkt_{seg}")
        segs.append(load_segment(d / f"mkt_{seg}"))
        conn.executemany("INSERT INTO mkt VALUES (?,?,?,?)", zip(
            cols["c_city"], cols["s_city"], map(int, cols["d_year"]),
            map(int, cols["rev"])))
    tpu = QueryExecutor(backend="tpu")
    tpu.add_table(SCHEMA, segs)
    host = QueryExecutor(backend="host")
    host.add_table(SCHEMA, segs)
    return tpu, host, conn, segs


def _plan(segment):
    query = parse_sql(SQL)
    query.filter = optimize_filter(query.filter)
    return SegmentPlanner(query, segment).plan()


def _rows(resp):
    assert not resp.exceptions, resp.exceptions
    return [(r[0], r[1], int(r[2])) + tuple(int(v) for v in r[3:])
            for r in resp.result_table.rows]


def test_every_segment_plans_the_sorted_table_of_its_own_dictionaries(env):
    _tpu, _host, _conn, segs = env
    programs = [_plan(seg) for seg in segs]
    cards = [tuple(d.cardinality for d in pl.group_dims) for pl in programs]
    assert cards[0] == (40, 40, 25)
    assert cards[5] == (39, 40, 25) and cards[9] == (40, 40, 24)
    for pl, card in zip(programs, cards):
        product = card[0] * card[1] * card[2]
        assert not mxu_groupby.supports(product + 1, 1)
        assert pl.program.mode == "group_by_sparse"
        assert pl.program.num_groups == table_bucket(product)
        assert pl.program.group_strides == (card[1] * card[2], card[2], 1)
        assert "3 keys c_city" in pl.group_table_reason
        assert f"= {product}," in pl.group_table_reason
        assert kernels.group_table_form(pl.program) == "sorted"
    # two LUT filters (the IN lists) and a range, as in `ssb16.flight3city`
    assert "lut0_lut1_rng_i2_by0x1x2" in program_label(programs[0].program)


def test_served_answer_equals_the_host_engines_and_sqlites(env):
    tpu, host, conn, _segs = env
    resp = tpu.execute_sql("SET trace = true; " + NOCACHE + SQL)
    got = _rows(resp)
    assert got == _rows(host.execute_sql(NOCACHE + SQL))
    want = [tuple(r) for r in conn.execute(
        "SELECT c_city, s_city, d_year, SUM(rev), COUNT(*), MIN(rev), "
        "MAX(rev) FROM mkt " + WHERE + "GROUP BY c_city, s_city, d_year "
        "ORDER BY d_year ASC, SUM(rev) DESC, c_city, s_city LIMIT 1000")]
    assert got == want and len(got) > 100
    assert not resp.num_groups_limit_reached
    # the per-segment stages ran: families of sorted tables (the segments
    # whose dictionaries differ are families of their own), no device merge
    spans = {}
    for s in resp.trace_info:
        spans.setdefault(s["operator"], []).append(
            s.get("attributes") or {})
    assert len(spans["family_dispatch"]) == 3 == resp.num_device_dispatches
    assert {a["groupTable"] for a in spans["family_dispatch"]} == {"sorted"}
    assert {a["groupSlots"] for a in spans["family_dispatch"]} == {
        table_bucket(40 * 40 * 25), table_bucket(39 * 40 * 25),
        table_bucket(40 * 40 * 24)}
    assert all("groupsCombined" not in a for a in spans["SERVER_COMBINE"])


def test_numgroupslimit_has_no_say_in_the_table(env):
    tpu, _host, _conn, _segs = env
    full = _rows(tpu.execute_sql(NOCACHE + SQL))
    resp = tpu.execute_sql("SET numGroupsLimit = 10; " + NOCACHE + SQL)
    assert _rows(resp) == full and not resp.num_groups_limit_reached


@pytest.mark.parametrize("seg", [0, 5, 9], ids=["all-cities", "a-city-missing",
                                                "a-year-missing"])
def test_sorted_table_equals_the_dense_table_of_the_plan_resized(env, seg):
    _tpu, _host, _conn, segs = env
    segment = segs[seg]
    plan = _plan(segment)
    product = 1
    for d in plan.group_dims:
        product *= d.cardinality
    dense = dataclasses.replace(plan.program, mode="group_by",
                                num_groups=product, key_space=0)
    view = SegmentDeviceView(segment)
    arrays, packed = plan.gather_arrays_packed(view)

    def run(program):
        return [np.asarray(o) for o in kernels.run_program(
            program, arrays, tuple(plan.params), segment.num_docs,
            padded=view.padded, packed=packed)]

    want, got = run(dense), run(plan.program)
    keys = got[-1]
    live = keys >= 0
    assert live.sum() == np.count_nonzero(want[0][:product]) > 20
    assert np.all(np.diff(keys[live]) > 0)  # a slot a group, in key order
    for sorted_col, dense_col in zip(got[:-1], want):
        table = np.zeros(product, dtype=dense_col.dtype)
        table[keys[live]] = sorted_col[:-1][live]
        occupied = want[0][:product] > 0
        assert np.array_equal(table[occupied], dense_col[:product][occupied])
    # rows the filter kept and no slot took: none (nothing is trimmed)
    assert got[0][-1] == 0
