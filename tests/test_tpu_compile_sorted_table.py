"""The drill-down's two top-N scans and flight 3's city-to-city scan,
compiled for a described TPU v5e (see test_tpu_compile.py and
tpu_compile_support.py): the sort-based scan with a slot for every key, as
one dispatch over 16 segments. A minute or more a case, so a file of their
own."""

import time

import numpy as np
import pytest

from pinot_tpu.engine.ir import program_label
from pinot_tpu.engine.plan import table_bucket
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.device_cache import SegmentDeviceView
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema
from pinot_tpu.spi.table_config import IndexingConfig, TableConfig
from tpu_compile_support import R22, compile_program


@pytest.mark.parametrize("sql,slots", [
    # dd_top_customers: an unsorted key, 291 thousand entries a segment
    pytest.param("SELECT p_brand, SUM(lo_revenue) FROM t WHERE lo_discount "
                 "BETWEEN 1 AND 3 GROUP BY p_brand ORDER BY SUM(lo_revenue) "
                 "DESC, p_brand LIMIT 20", 1 << 19, id="sort-2^19"),
    # dd_top_orders: the key ascends in doc order, 1.05 million a segment
    pytest.param("SELECT lo_orderkey, SUM(lo_quantity), SUM(lo_extendedprice)"
                 " FROM t WHERE lo_discount BETWEEN 1 AND 3 GROUP BY "
                 "lo_orderkey ORDER BY SUM(lo_quantity) DESC, lo_orderkey "
                 "LIMIT 100", 1 << 20, id="presorted-2^20"),
])
def test_whole_table_sparse_family_compiles(one_chip, ssb, sql, slots):
    """The sort-based scan with a slot for every key of the dictionary (the
    planner's rule above the limb kernel's table), as one dispatch over
    16 x 2^22 rows (`lax.map` over the members): the drill-down's two top-N
    programs. On the chip's machine (PR 30): 34.7 and 47.6 s; here 48-56
    and 80-85."""
    t0 = time.perf_counter()
    program, _ = compile_program(
        one_chip, ssb, "SET sparseGroupBy = true; " + sql, R22, batch=16,
        sparse_groups=slots, whole_table=True)
    took = time.perf_counter() - t0
    print(f"sorted scan at {slots} slots x 16: compiled in {took:.1f} s")
    assert program.mode == "group_by_sparse" and program.num_groups == slots
    assert program.keys_presorted == (slots == 1 << 20)
    assert took < 300


@pytest.fixture(scope="module")
def flight3(tmp_path_factory):
    """A small segment with the key columns of SSB's flight 3 at their
    published cardinalities (250 cities a side as strings, 7 years) and
    `lo_revenue` raw: the plan made from it has the cell's shapes."""
    n = 1 << 15
    i = np.arange(n)
    schema = Schema.build(
        "t", dimensions=[("c_city", "STRING"), ("s_city", "STRING"),
                         ("d_year", "INT")],
        metrics=[("lo_revenue", "INT")])
    cfg = TableConfig(table_name="t", indexing=IndexingConfig(
        no_dictionary_columns=["lo_revenue"]))
    cities = np.asarray([f"NATION{c // 10:03d}{c % 10}" for c in range(250)],
                        dtype=object)
    cols = {"c_city": cities[i % 250], "s_city": cities[(i // 7) % 250],
            "d_year": (1992 + i % 7).astype(np.int32),
            "lo_revenue": (1 + i * 37 % 600_000).astype(np.int32)}
    path = str(tmp_path_factory.mktemp("tpu_compile_f3") / "s")
    SegmentBuilder(schema, cfg, "s0").build(cols, path)
    segment = load_segment(path)
    return segment, SegmentDeviceView(segment)


def test_three_key_sorted_table_family_compiles(one_chip, flight3):
    """`ssb16.flight3city`'s Q3.3 as the planner makes it since PR 35: the
    sorted table of `c_city x s_city x d_year` (437,500 combinations,
    458,752 slots) by the planner's own rule (no SET), a 32-bit composite
    key, two LUT filters and a range batched before the loop, one 32-bit
    payload through the sort, one dispatch over 16 x 2^22 rows."""
    sql = ("SELECT SUM(lo_revenue), c_city, s_city, d_year FROM t WHERE "
           "c_city IN ('NATION0191', 'NATION0195') AND s_city IN "
           "('NATION0191', 'NATION0195') AND d_year BETWEEN 1993 "
           "AND 1997 GROUP BY c_city, s_city, d_year ORDER BY d_year "
           "ASC, SUM(lo_revenue) DESC, c_city, s_city LIMIT 25")
    t0 = time.perf_counter()
    program, text = compile_program(one_chip, flight3, sql, R22, batch=16)
    took = time.perf_counter() - t0
    print(f"three-key sorted scan x 16: compiled in {took:.1f} s")
    assert program.mode == "group_by_sparse"
    assert program.num_groups == table_bucket(250 * 250 * 7) == 458_752
    assert program.key_space == 1 << 19 and not program.keys_presorted
    assert program.group_strides == (1750, 7, 1)
    assert [a.kind for a in program.aggs] == ["sum"]
    assert program_label(program) == "gbs_and3_lut0_lut1_rng_i2_by0x1x2_sum_c3"
    # the route of `dd_top_customers`: 32-bit sorts only, nothing 64-bit
    # through a sort, and no scatter left in the scan
    assert "s64[" not in "".join(
        line for line in text.splitlines() if " sort(" in line)
    assert " scatter(" not in text
    assert took < 300
