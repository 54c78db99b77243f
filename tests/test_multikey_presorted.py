"""Multi-key presorted detection (ISSUE 3 satellite).

Segments record their ingestion sort order as a lexicographic co-sort
chain (`SegmentMetadata.sort_order`, computed at build from the forward
indexes); the planner marks COMPOSITE group keys presorted when they are
an exact in-order prefix of that chain. Row-major composite keys
(Σ id_i·stride_i) of a lexicographically nondecreasing id sequence are
nondecreasing, so the existing zero-sort presorted kernel applies with no
kernel change — pinned here by tracing the jaxpr.
"""

from __future__ import annotations

import numpy as np
import pytest

from pinot_tpu.engine.plan import SegmentPlanner
from pinot_tpu.engine.query_executor import QueryExecutor
from pinot_tpu.query.parser.sql import parse_sql
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema

from test_sparse_groupby_perf import (_grouping_sorts, _jaxpr_for,
                                      _table_sorts)

SCHEMA = Schema.build(
    "mk",
    dimensions=[("a", "INT"), ("b", "INT"), ("c", "INT")],
    metrics=[("v", "LONG")])
N = 4096
FORCE = "SET sparseGroupBy = true; "


def _build(tmp_path, lexsorted: bool):
    rng = np.random.default_rng(11)
    cols = {
        "a": rng.integers(0, 8, N).astype(np.int32),
        "b": rng.integers(0, 8, N).astype(np.int32),
        "c": rng.integers(0, 1000, N).astype(np.int32),
        "v": rng.integers(0, 1000, N).astype(np.int64),
    }
    if lexsorted:
        order = np.lexsort((cols["b"], cols["a"]))  # by a, then b
        cols = {n: x[order] for n, x in cols.items()}
    name = "lex" if lexsorted else "shuf"
    SegmentBuilder(SCHEMA, segment_name=name).build(cols, tmp_path / name)
    return load_segment(tmp_path / name)


@pytest.fixture()
def lexseg(tmp_path):
    return _build(tmp_path, lexsorted=True)


def test_builder_records_sort_order_chain(tmp_path):
    seg = _build(tmp_path, lexsorted=True)
    # (a, b) co-sorted; c is random inside the (a, b) runs so the chain
    # must stop at b — and the chain survives the metadata.json round trip
    assert seg.metadata.sort_order == ["a", "b"]


def test_unsorted_segment_has_empty_chain(tmp_path):
    seg = _build(tmp_path, lexsorted=False)
    assert seg.metadata.sort_order == []


def _presorted(seg, group_by):
    q = parse_sql(FORCE + f"SELECT {group_by}, SUM(v) FROM mk "
                          f"GROUP BY {group_by} LIMIT 100000")
    p = SegmentPlanner(q, seg).plan().program
    assert p.mode == "group_by_sparse"
    return p.keys_presorted


def test_composite_prefix_is_presorted(lexseg):
    assert _presorted(lexseg, "a, b")
    assert _presorted(lexseg, "a")  # single key: is_sorted metadata


def test_non_prefix_orders_are_not(lexseg):
    # order matters (b, a is NOT lexicographically nondecreasing), gaps
    # matter (a, c skips b), and extending past the chain disqualifies
    assert not _presorted(lexseg, "b, a")
    assert not _presorted(lexseg, "a, c")
    assert not _presorted(lexseg, "a, b, c")
    assert not _presorted(lexseg, "b")


def test_composite_presorted_compiles_with_zero_sorts(lexseg):
    program, jaxpr = _jaxpr_for(
        lexseg, FORCE + "SELECT a, b, SUM(v), COUNT(*) FROM mk "
                        "GROUP BY a, b LIMIT 100000")
    assert program.keys_presorted
    assert _grouping_sorts(jaxpr) == []  # the rows are never sorted by key
    assert len(_table_sorts(jaxpr)) == 1


def test_composite_presorted_results_match_host(tmp_path):
    segs = [_build(tmp_path, lexsorted=True)]
    tpu = QueryExecutor(backend="tpu")
    host = QueryExecutor(backend="host")
    for qe in (tpu, host):
        qe.add_table(SCHEMA, segs)
    for gb in ("a, b", "a, b, c"):
        sql = (FORCE + f"SELECT {gb}, COUNT(*), SUM(v) FROM mk "
                       f"GROUP BY {gb} ORDER BY {gb} LIMIT 100000")
        rt, rh = tpu.execute_sql(sql), host.execute_sql(sql)
        assert not rt.exceptions and not rh.exceptions, (
            rt.exceptions, rh.exceptions)
        to_int = lambda rows: [tuple(map(int, r)) for r in rows]
        assert to_int(rt.result_table.rows) == to_int(rh.result_table.rows)
