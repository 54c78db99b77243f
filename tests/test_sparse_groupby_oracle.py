"""Oracle matrix for the sparse group-by fast paths (ISSUE 2).

Every cell of {COUNT, SUM, MIN, MAX, DISTINCTCOUNT} ×
{presorted key, shuffled key} × {untrimmed, numGroupsLimit trim} ×
multi-segment is checked against sqlite on the SAME rows, and the
device-side sparse combine is checked bit-for-bit (int aggs) against the
host merge (`SET deviceCombine = false`) — the two merge paths must be
indistinguishable from the result tables.

The test cardinality is tiny (dense-eligible), so every query rides the
`SET sparseGroupBy = true` escape hatch to reach the sparse kernel.
"""

from __future__ import annotations

import sqlite3

import numpy as np
import pytest

from pinot_tpu.engine.plan import SegmentPlanner
from pinot_tpu.engine.query_executor import QueryExecutor
from pinot_tpu.query.parser.sql import parse_sql
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema

N = 6000
N_KEYS = 300
SCHEMA = Schema.build(
    "okv",
    dimensions=[("k", "INT"), ("d", "INT")],
    metrics=[("v", "LONG")])

FORCE = "SET sparseGroupBy = true; "
MATRIX_SQL = (
    "SELECT k, COUNT(*), SUM(v), MIN(v), MAX(v), DISTINCTCOUNT(d) "
    "FROM okv {where}GROUP BY k ORDER BY k LIMIT 100000")
ORACLE_SQL = (
    "SELECT k, COUNT(*), SUM(v), MIN(v), MAX(v), COUNT(DISTINCT d) "
    "FROM okv {where}GROUP BY k ORDER BY k")


def _build_env(tmp_path_factory, presorted: bool):
    rng = np.random.default_rng(42)
    data = {
        "k": rng.integers(0, N_KEYS, N).astype(np.int32),
        "d": rng.integers(0, 16, N).astype(np.int32),
        "v": rng.integers(-500, 5000, N).astype(np.int64),
    }
    d = tmp_path_factory.mktemp("sorted" if presorted else "shuffled")
    half = N // 2
    segs = []
    for i, sl in enumerate([slice(0, half), slice(half, N)]):
        part = {c: a[sl] for c, a in data.items()}
        if presorted:
            # sortedness is a per-segment metadata property: sorting each
            # slice independently keeps the global multiset identical to
            # the shuffled fixture's
            order = np.argsort(part["k"], kind="stable")
            part = {c: a[order] for c, a in part.items()}
        SegmentBuilder(SCHEMA, segment_name=f"s{i}").build(part, d / f"s{i}")
        segs.append(load_segment(d / f"s{i}"))
    tpu = QueryExecutor(backend="tpu")
    tpu.add_table(SCHEMA, segs)
    conn = sqlite3.connect(":memory:")
    conn.execute("CREATE TABLE okv (k INT, d INT, v INT)")
    conn.executemany("INSERT INTO okv VALUES (?,?,?)", zip(
        map(int, data["k"]), map(int, data["d"]), map(int, data["v"])))
    return tpu, conn, segs


@pytest.fixture(scope="module", params=[True, False],
                ids=["presorted", "shuffled"])
def env(request, tmp_path_factory):
    return (*_build_env(tmp_path_factory, request.param), request.param)


def _int_rows(resp):
    assert not resp.exceptions, resp.exceptions
    return [tuple(int(v) for v in row) for row in resp.result_table.rows]


def test_planner_path_matches_fixture(env):
    tpu, conn, segs, presorted = env
    q = parse_sql(FORCE + MATRIX_SQL.format(where=""))
    for seg in segs:
        p = SegmentPlanner(q, seg).plan().program
        assert p.mode == "group_by_sparse"
        assert p.keys_presorted == presorted


def test_agg_matrix_vs_sqlite(env):
    tpu, conn, segs, presorted = env
    got = _int_rows(tpu.execute_sql(FORCE + MATRIX_SQL.format(where="")))
    want = [tuple(int(v) for v in row)
            for row in conn.execute(ORACLE_SQL.format(where=""))]
    assert got == want


def test_agg_matrix_with_filter_vs_sqlite(env):
    # a filter leaves masked rows INSIDE key runs — the presorted path must
    # skip them via op identities, not by moving rows
    tpu, conn, segs, presorted = env
    got = _int_rows(tpu.execute_sql(
        FORCE + MATRIX_SQL.format(where="WHERE v > 100 AND d < 12 ")))
    want = [tuple(int(v) for v in row) for row in conn.execute(
        ORACLE_SQL.format(where="WHERE v > 100 AND d < 12 "))]
    assert got == want


@pytest.mark.parametrize("where", ["WHERE d < 3 ", "WHERE d < 1 AND v < 0 "],
                         ids=["a-fifth", "a-hundredth"])
def test_a_selective_filter_takes_the_short_tail_vs_sqlite(env, where):
    # the sorted path's rows after the sort are a branch on how many the
    # filter kept: an eighth of the padded rows (here a half: 2,048 of
    # 4,096) or fewer run scans and the table's sort over that prefix alone
    tpu, conn, segs, presorted = env
    got = _int_rows(tpu.execute_sql(FORCE + MATRIX_SQL.format(where=where)))
    want = [tuple(int(v) for v in row)
            for row in conn.execute(ORACLE_SQL.format(where=where))]
    assert got == want and 0 < len(got)


def test_trimmed_groups_stay_exact(env):
    tpu, conn, segs, presorted = env
    resp = tpu.execute_sql(
        FORCE + "SET numGroupsLimit = 40; " + MATRIX_SQL.format(where=""))
    assert not resp.exceptions, resp.exceptions
    assert resp.num_groups_limit_reached
    got = _int_rows(resp)
    assert 0 < len(got) <= 2 * 40  # per-segment cap; merge can reach 2x
    want = {row[0]: tuple(map(int, row))
            for row in conn.execute(ORACLE_SQL.format(where=""))}
    for row in got:
        # the sort-order trim keeps each surviving group COMPLETE within a
        # segment; a group surviving in both segments is globally exact
        assert row[0] in want
    # the low keys sort first, so the smallest surviving keys are complete
    # in both segments and must match sqlite exactly
    exact = [r for r in got[:40] if r == want[r[0]]]
    assert exact, "trim kept no globally-exact group"


def test_device_combine_bit_identical_to_host_merge(env):
    tpu, conn, segs, presorted = env
    for where in ("", "WHERE v > 100 "):
        sql = MATRIX_SQL.format(where=where)
        dev = tpu.execute_sql(FORCE + sql)
        host = tpu.execute_sql(FORCE + "SET deviceCombine = false; " + sql)
        assert not dev.exceptions and not host.exceptions
        # int aggs: bit-for-bit across the two merge implementations
        assert _int_rows(dev) == _int_rows(host)
        assert dev.num_docs_scanned == host.num_docs_scanned


def test_device_combine_under_trim_matches_host_merge(env):
    tpu, conn, segs, presorted = env
    sql = "SET numGroupsLimit = 40; " + MATRIX_SQL.format(where="")
    dev = tpu.execute_sql(FORCE + sql)
    host = tpu.execute_sql(FORCE + "SET deviceCombine = false; " + sql)
    assert not dev.exceptions and not host.exceptions
    assert _int_rows(dev) == _int_rows(host)
    assert dev.num_groups_limit_reached == host.num_groups_limit_reached


def test_single_agg_cells_vs_sqlite(env):
    # each agg alone (different payload counts route differently: 1 payload
    # sorts (key, payload); >=2 payloads take the iota gather)
    tpu, conn, segs, presorted = env
    for fn, oracle_fn in [("COUNT(*)", "COUNT(*)"), ("SUM(v)", "SUM(v)"),
                          ("MIN(v)", "MIN(v)"), ("MAX(v)", "MAX(v)"),
                          ("DISTINCTCOUNT(d)", "COUNT(DISTINCT d)")]:
        got = _int_rows(tpu.execute_sql(
            FORCE + f"SELECT k, {fn} FROM okv GROUP BY k "
                    "ORDER BY k LIMIT 100000"))
        want = [tuple(int(v) for v in row) for row in conn.execute(
            f"SELECT k, {oracle_fn} FROM okv GROUP BY k ORDER BY k")]
        assert got == want, fn
