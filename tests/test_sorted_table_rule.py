"""The planner's choice between the dense group table and the sorted one
(`engine/plan.SegmentPlanner._sorted_table_rule`), at the rule's edges.

One identifier key over an integer dictionary with columnar count / sum /
min / max aggregations gets a sorted table that holds every key, from
`mxu_groupby.MAX_GROUPS` keys (the first size the limb kernel leaves) up to
`DENSE_GROUP_LIMIT`; above that the table is sorted as before, cut at
numGroupsLimit. Every other shape stays where it was. `EXPLAIN
IMPLEMENTATION` names the path and the reason.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

from pinot_tpu.engine import plan as planmod
from pinot_tpu.engine.plan import (DEFAULT_NUM_GROUPS_LIMIT,
                                   DENSE_GROUP_LIMIT, SegmentPlanner,
                                   share_table_size, table_bucket)
from pinot_tpu.engine.query_executor import QueryExecutor
from pinot_tpu.ops import mxu_groupby
from pinot_tpu.query.parser.sql import parse_sql
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import DataType, FieldSpec, FieldType, Schema

SCHEMA = Schema.build(
    "rule",
    dimensions=[("k", "INT"), ("s", "STRING"), ("d", "INT"), ("y", "INT")],
    metrics=[("v", "INT")])
MV_SCHEMA = Schema.build("rulemv", metrics=[("v", "INT")])
MV_SCHEMA.add_field(FieldSpec("k", DataType.INT, FieldType.DIMENSION,
                              single_value=False))

SUM = "SELECT k, SUM(v) FROM rule GROUP BY k ORDER BY SUM(v) DESC, k LIMIT 10"


@functools.lru_cache(maxsize=None)
def _segment(tmp, keys: int):
    """A segment whose column `k` has exactly `keys` distinct integers
    (every key once), `s` the same keys as strings, `d` 8 and `y` 40
    distinct values."""
    k = np.arange(keys, dtype=np.int32)
    cols = {"k": k, "s": np.char.add("c", k.astype(str)).astype(object),
            "d": (k % 8).astype(np.int32), "y": (k % 40).astype(np.int32),
            "v": (k % 1000).astype(np.int32)}
    path = f"{tmp}/rule_{keys}"
    SegmentBuilder(SCHEMA, segment_name=f"rule_{keys}").build(cols, path)
    return load_segment(path)


@pytest.fixture(scope="module")
def tmp(tmp_path_factory):
    return str(tmp_path_factory.mktemp("rule"))


def _program(segment, sql: str):
    return SegmentPlanner(parse_sql(sql), segment).plan()


LIMB = mxu_groupby.MAX_GROUPS - 1  # the limb kernel's table has a trash slot


@pytest.mark.parametrize("keys,mode,slots", [
    pytest.param(LIMB, "group_by", LIMB, id="the-limb-kernels-last"),
    pytest.param(LIMB + 1, "group_by_sparse", table_bucket(LIMB + 1),
                 id="at-MAX_GROUPS-the-crossover"),
    pytest.param(LIMB + 2, "group_by_sparse", table_bucket(LIMB + 2),
                 id="one-above"),
    pytest.param(300_000, "group_by_sparse", table_bucket(300_000),
                 id="a-dictionary-of-the-cells-size"),
    pytest.param(DENSE_GROUP_LIMIT, "group_by_sparse", DENSE_GROUP_LIMIT,
                 id="at-DENSE_GROUP_LIMIT"),
    pytest.param(DENSE_GROUP_LIMIT + 1, "group_by_sparse",
                 DEFAULT_NUM_GROUPS_LIMIT, id="above-DENSE_GROUP_LIMIT"),
])
def test_one_integer_key_is_sorted_between_the_limb_table_and_the_dense_limit(
        tmp, keys, mode, slots):
    plan = _program(_segment(tmp, keys), SUM)
    assert plan.program.mode == mode
    assert plan.program.num_groups == slots
    # a table by the rule holds the whole dictionary: nothing to trim
    if mode == "group_by_sparse" and keys <= DENSE_GROUP_LIMIT:
        assert plan.program.num_groups >= keys
        assert "above the limb kernel" in plan.group_table_reason


KEYS = 40_000  # above MAX_GROUPS, far below DENSE_GROUP_LIMIT


@pytest.mark.parametrize("sql,why", [
    pytest.param("SELECT s, SUM(v) FROM rule GROUP BY s LIMIT 10",
                 "not of integers", id="a-string-key"),
    pytest.param("SELECT k, y, SUM(v) FROM rule GROUP BY k, y LIMIT 10",
                 "2 keys", id="two-keys"),
    pytest.param("SELECT k, DISTINCTCOUNT(d) FROM rule GROUP BY k LIMIT 10",
                 "distinct_bitmap needs the dense table",
                 id="a-distinct-bitmap"),
    pytest.param("SELECT k + 1, SUM(v) FROM rule GROUP BY k + 1 LIMIT 10",
                 None, id="a-derived-key"),
])
def test_other_shapes_stay_dense(tmp, sql, why):
    try:
        plan = _program(_segment(tmp, KEYS), sql)
    except planmod.UnsupportedQueryError:
        assert why is None  # the host's shape, as before
        return
    assert plan.program.mode == "group_by"
    if why is not None:
        assert why in plan.group_table_reason


def test_a_multi_value_key_stays_dense(tmp):
    n = KEYS
    cols = {"k": [[i, (i + 1) % n] for i in range(n)],
            "v": np.arange(n, dtype=np.int32)}
    path = f"{tmp}/rulemv"
    SegmentBuilder(MV_SCHEMA, segment_name="rulemv").build(cols, path)
    plan = _program(load_segment(path),
                    "SELECT k, SUM(v) FROM rulemv GROUP BY k LIMIT 10")
    assert plan.program.mode == "group_by"
    assert "multi-value" in plan.group_table_reason


def test_set_sparse_group_by_keeps_its_meaning(tmp):
    plan = _program(_segment(tmp, KEYS),
                    "SET sparseGroupBy = true; SET numGroupsLimit = 1000; "
                    + SUM)
    assert plan.program.mode == "group_by_sparse"
    assert plan.program.num_groups == 1000
    assert plan.group_table_reason == "sparseGroupBy=true"


def test_an_ordered_prefix_still_trims_exactly(tmp):
    plan = _program(_segment(tmp, KEYS),
                    "SELECT k, SUM(v) FROM rule GROUP BY k ORDER BY k "
                    "LIMIT 25")
    assert plan.program.mode == "group_by_sparse"
    assert plan.program.exact_trim and plan.program.num_groups == 25


def test_tables_of_one_query_share_the_largest_size(tmp):
    # 65,400 and 65,700 keys lie on either side of 2^16
    plans = [_program(_segment(tmp, keys), SUM) for keys in (65_400, 65_700)]
    sizes = [pl.program.num_groups for pl in plans]
    assert sizes == [table_bucket(65_400), table_bucket(65_700)]
    assert sizes[0] < sizes[1]
    shared = share_table_size(plans)
    assert shared[0].program == shared[1].program
    assert shared[0].program.num_groups == sizes[1]
    assert shared[1] is plans[1]
    # plans that differ in more than the size are left alone
    other = _program(_segment(tmp, 65_400),
                     SUM.replace("SUM(v)", "MAX(v)"))
    assert share_table_size([plans[0], other]) == [plans[0], other]


@pytest.mark.parametrize("keys,path,why", [
    pytest.param(LIMB, "path:dense", "fit the limb kernel", id="dense"),
    pytest.param(KEYS, "path:sparse-presorted", "above the limb kernel",
                 id="sorted"),
])
def test_explain_implementation_names_the_path_and_the_reason(
        tmp, keys, path, why):
    qe = QueryExecutor(backend="tpu")
    qe.add_table(SCHEMA, [_segment(tmp, keys)])
    resp = qe.execute_sql("EXPLAIN IMPLEMENTATION FOR " + SUM)
    assert not resp.exceptions, resp.exceptions
    kernel = [r[0] for r in resp.result_table.rows
              if r[0].startswith("DEVICE_KERNEL")]
    assert len(kernel) == 1
    assert path in kernel[0] and why in kernel[0]
    plain = qe.execute_sql("EXPLAIN PLAN FOR " + SUM)
    assert "why:" not in "".join(r[0] for r in plain.result_table.rows)
