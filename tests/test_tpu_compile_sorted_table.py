"""The drill-down's two top-N scans, compiled for a described TPU v5e (see
test_tpu_compile.py and tpu_compile_support.py): the sort-based scan with a
slot for every key, as one dispatch over 16 segments. A minute or more a
case, so a file of their own."""

import time

import pytest

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
