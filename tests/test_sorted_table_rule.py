"""The planner's choice between the dense group table and the sorted one
(`engine/plan.SegmentPlanner._sorted_table_rule`), at the rule's edges.

Identifier keys over single-value dictionary columns (one or several, of
any type) with columnar count / sum / min / max aggregations get a sorted
table that holds every key, from `mxu_groupby.MAX_GROUPS` slots (the first
size the limb kernel leaves) up to `DENSE_GROUP_LIMIT`; above that the
table is sorted as before, cut at numGroupsLimit. What the sorted kernel
refuses (a DISTINCTCOUNT bitmap, a derived or multi-value key) stays
dense. `EXPLAIN IMPLEMENTATION` names the path and the reason.
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
    dimensions=[("k", "INT"), ("s", "STRING"), ("t", "STRING"), ("d", "INT"),
                ("y", "INT"), ("w", "INT")],
    metrics=[("v", "INT")])
MV_SCHEMA = Schema.build("rulemv", metrics=[("v", "INT")])
MV_SCHEMA.add_field(FieldSpec("k", DataType.INT, FieldType.DIMENSION,
                              single_value=False))

SUM = "SELECT k, SUM(v) FROM rule GROUP BY k ORDER BY SUM(v) DESC, k LIMIT 10"


@functools.lru_cache(maxsize=None)
def _segment(tmp, keys: int):
    """A segment whose column `k` has exactly `keys` distinct integers
    (every key once), `s` and `t` the same keys as strings, `d` 8, `y` 40
    and `w` 7 distinct values."""
    k = np.arange(keys, dtype=np.int32)
    cols = {"k": k, "s": np.char.add("c", k.astype(str)).astype(object),
            "t": np.char.add("t", k.astype(str)).astype(object),
            "d": (k % 8).astype(np.int32), "y": (k % 40).astype(np.int32),
            "w": (k % 7).astype(np.int32),
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


@pytest.mark.parametrize("keys,sql,product,names", [
    pytest.param(KEYS, "SELECT s, SUM(v) FROM rule GROUP BY s LIMIT 10",
                 KEYS, "one key of 40000 entries", id="a-string-key"),
    pytest.param(KEYS, "SELECT k, y, SUM(v) FROM rule GROUP BY k, y "
                 "LIMIT 10", KEYS * 40,
                 "2 keys k[40000] x y[40] = 1600000", id="two-keys"),
    # the shape of SSB's Q3.3 (c_city x s_city x d_year): two string keys
    # of 250 and one integer key of 7
    pytest.param(250, "SELECT s, t, w, SUM(v), COUNT(*), MIN(v), MAX(v) "
                 "FROM rule GROUP BY s, t, w LIMIT 10", 250 * 250 * 7,
                 "3 keys s[250] x t[250] x w[7] = 437500",
                 id="three-keys-250x250x7"),
])
def test_several_keys_and_string_keys_are_sorted(tmp, keys, sql, product,
                                                 names):
    plan = _program(_segment(tmp, keys), sql)
    assert plan.program.mode == "group_by_sparse"
    # a slot for every combination of the keys: nothing to trim
    assert plan.program.num_groups == table_bucket(product) >= product
    assert not plan.program.exact_trim and not plan.program.keys_presorted
    assert 0 < plan.program.key_space < 1 << 31  # a 32-bit composite key
    assert names in plan.group_table_reason
    assert "above the limb kernel" in plan.group_table_reason
    # numGroupsLimit has no say in a table by the rule
    cut = _program(_segment(tmp, keys), "SET numGroupsLimit = 1000; " + sql)
    assert cut.program == plan.program


@pytest.mark.parametrize("keys,sql,why", [
    pytest.param(KEYS, "SELECT k, DISTINCTCOUNT(d) FROM rule GROUP BY k "
                 "LIMIT 10", "distinct_bitmap needs the dense table",
                 id="a-distinct-bitmap"),
    pytest.param(250, "SELECT s, t, DISTINCTCOUNT(d) FROM rule GROUP BY s, t "
                 "LIMIT 10", "distinct_bitmap needs the dense table",
                 id="two-keys-a-distinct-bitmap"),
    pytest.param(KEYS, "SELECT k + 1, SUM(v) FROM rule GROUP BY k + 1 "
                 "LIMIT 10", None, id="a-derived-key"),
    pytest.param(250, "SELECT s, k + 1, SUM(v) FROM rule GROUP BY s, k + 1 "
                 "LIMIT 10", "a derived key", id="a-derived-key-of-two"),
])
def test_other_shapes_stay_dense(tmp, keys, sql, why):
    try:
        plan = _program(_segment(tmp, keys), sql)
    except planmod.UnsupportedQueryError:
        assert why is None  # the host's shape, as before
        return
    assert plan.program.mode == "group_by"
    assert not mxu_groupby.supports(plan.program.num_groups + 1, 1)
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
    # the LIMIT rounded up to a power of two: one program for LIMITs 17-32
    assert plan.program.exact_trim and plan.program.num_groups == 32


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


THREE = ("SELECT s, t, w, SUM(v) FROM rule GROUP BY s, t, w "
         "ORDER BY w, SUM(v) DESC, s, t LIMIT 10")


@pytest.mark.parametrize("keys,sql,path,why,combine", [
    pytest.param(LIMB, SUM, "path:dense", "fit the limb kernel",
                 "host-columnar-scatter", id="dense"),
    pytest.param(KEYS, SUM, "path:sparse-presorted", "above the limb kernel",
                 "device-sparse(concat+edge-reduce)", id="sorted"),
    # several keys: the same scan, and the host's merge of the segments'
    # tables as for the dense table (the device merge takes one integer key)
    pytest.param(250, THREE, "path:sparse-sort",
                 "why:3 keys s[250] x t[250] x w[7] = 437500, above the "
                 "limb kernel", "host-columnar-scatter",
                 id="three-keys-sorted"),
])
def test_explain_implementation_names_the_path_and_the_reason(
        tmp, keys, sql, path, why, combine):
    qe = QueryExecutor(backend="tpu")
    qe.add_table(SCHEMA, [_segment(tmp, keys), _segment(tmp, keys)])
    resp = qe.execute_sql("EXPLAIN IMPLEMENTATION FOR " + sql)
    assert not resp.exceptions, resp.exceptions
    lines = [r[0] for r in resp.result_table.rows]
    kernel = [line for line in lines if line.startswith("DEVICE_KERNEL")]
    assert len(kernel) == 1
    assert path in kernel[0] and why in kernel[0]
    merge = [line for line in lines if line.startswith("SERVER_COMBINE")]
    assert len(merge) == 1 and f"impl:{combine}," in merge[0]
    plain = qe.execute_sql("EXPLAIN PLAN FOR " + sql)
    assert "why:" not in "".join(r[0] for r in plain.result_table.rows)


def test_the_sweeps_key_columns_multiply_to_the_table(tmp):
    # `tools/groupby_crossover_sweep.py --key-columns N`: the composite's
    # cardinalities multiply to `--keys`, and the re-sized programs of both
    # forms answer alike (the rehearsal's toy rows)
    from pinot_tpu.tools import groupby_crossover_sweep as sweep

    for keys, n in ((458_752, 3), (1_835_008, 3), (40_000, 2), (32_768, 1)):
        cards = sweep.key_cards(keys, n)
        assert len(cards) == n and int(np.prod(cards)) == keys
    out = f"{tmp}/sweep.jsonl"
    assert sweep.main(["--rehearse", "--keys", "40000", "--key-columns", "3",
                       "--factors", "0.25,1.0", "--reps", "1",
                       "--out", out]) == 0
    import json

    lines = [json.loads(line) for line in open(out)]
    assert [(c["form"], c["factor"]) for c in lines] == [
        ("dense", 0.25), ("sorted", 0.25), ("dense", 1.0), ("sorted", 1.0)]
    assert all(c["equal"] and c["cards"] == [32, 25, 50] for c in lines)
    assert {c["slots"] for c in lines} == {40_000, table_bucket(40_000)}
