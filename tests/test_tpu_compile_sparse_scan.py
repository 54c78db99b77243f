"""The sort-based scan of one high-cardinality segment, compiled for a
described TPU v5e (see test_tpu_compile.py and tpu_compile_support.py):
a minute a case, so a file of their own."""

import time

import pytest

from tpu_compile_support import R24, compile_program


@pytest.mark.parametrize("sql", [
    pytest.param("SELECT lo_orderkey, SUM(lo_revenue), COUNT(*) FROM t "
                 "GROUP BY lo_orderkey ORDER BY lo_orderkey LIMIT 100000",
                 id="presorted-sum-count"),
    pytest.param("SELECT lo_orderkey, DISTINCTCOUNT(lo_discount), "
                 "SUM(lo_revenue) FROM t GROUP BY lo_orderkey "
                 "ORDER BY lo_orderkey LIMIT 100000",
                 id="presorted-distinct"),
    pytest.param("SELECT p_brand, lo_discount, SUM(lo_revenue), "
                 "MIN(lo_quantity) FROM t GROUP BY p_brand, lo_discount "
                 "LIMIT 100000", id="sort-gather"),
])
def test_sparse_group_by_compiles(one_chip, ssb, sql):
    """High-cardinality (sort/scan-based) group-by at a 16M-row segment
    with a 4M-key dictionary. Guards compile TIME as much as acceptance:
    the chip's compiler needs minutes for jnp.cumsum / associative_scan at
    this n, seconds for the shift scans kernels._prefix_sum uses (a
    lax.sort costs it 20-60 s whatever n is). The bound is wide because
    five other workers share the host; it guards the cliff."""
    t0 = time.perf_counter()
    program, _ = compile_program(
        one_chip, ssb, "SET sparseGroupBy = true; " + sql, R24,
        sparse_groups=1 << 22)
    assert program.mode == "group_by_sparse"
    assert time.perf_counter() - t0 < 300  # did not end in 300 s with cumsum
