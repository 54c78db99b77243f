"""Compile the main path's programs for a described TPU v5e, without a chip.

The TPU compiler is installed beside JAX and compiles for a topology that
is described, not attached. Interpret-mode and CPU-branch tests cannot see
what it refuses (an i64 inside a Pallas kernel, a vector op the chip lacks,
VMEM at the widest accumulator), so these cases guard the kernels of the
served path at real shapes. Nothing runs: a compile that passes says
nothing about results or speed.

This file holds the limb and fused kernels, the dense, selection and batch
programs and the output pack: seconds each. The cases that cost the
compiler a minute or more each are in files of their own, so that
`--dist loadfile` spreads them over workers: test_tpu_compile_sparse_scan.py
(the sort-based scan, one segment), test_tpu_compile_sorted_table.py (the
drill-down's two whole-table scans), test_tpu_compile_merge_cut.py,
test_tpu_compile_merge.py and test_tpu_compile_merge_values.py (the three
device merges). tpu_compile_support.py has what they share and says when
several processes may describe the chip at once; conftest.py starts the
long files first.
"""

import re
import time

import jax
import jax.numpy as jnp
import pytest

from pinot_tpu.engine import ir
from pinot_tpu.ops import kernels, mxu_groupby
from tpu_compile_support import R20, R22, R24, compile_program, spec

# -- the limb kernel ---------------------------------------------------------

# widest plane count per limb dtype (mxu_groupby.MAX_PLANES for each)
_WIDEST = {"int8": 24, "bfloat16": 16}


def _limb_cases():
    for dt in ("int8", "bfloat16"):
        p = _WIDEST[dt]
        yield pytest.param(dt, R20, 7000, 7, id=f"{dt}-2^20-G7000-P7")
        yield pytest.param(dt, R24, 7000, p, id=f"{dt}-2^24-G7000-P{p}")
        yield pytest.param(dt, R20, 8, 7, id=f"{dt}-2^20-G8-P7")
        # widest accumulators supports() admits: planes * s1 <= 4096
        yield pytest.param(dt, R20, 32768, 16, id=f"{dt}-2^20-G32768-P16")
    yield pytest.param("int8", R20, 128 * 170, 24, id="int8-2^20-G21760-P24")


@pytest.mark.parametrize("dtype,n,groups,planes", _limb_cases())
def test_limb_kernel_compiles(one_chip, dtype, n, groups, planes):
    assert groups <= mxu_groupby.MAX_GROUPS
    assert planes * max(1, -(-groups // mxu_groupby.LANES)) <= 4096
    specs = tuple(spec(one_chip, (n,), jnp.dtype(dtype))
                  for _ in range(planes))
    compiled = mxu_groupby._pallas_limb_sums.lower(
        specs, spec(one_chip, (n,), jnp.int32),
        num_segments=groups).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- engine programs ---------------------------------------------------------


def _op_count(text: str, op: str) -> int:
    return len(re.findall(rf"= \S+ {op}\(", text))


_GROUP2 = ("SELECT d_year, p_brand, SUM(lo_revenue), COUNT(*) FROM t "
           "WHERE {where} GROUP BY d_year, p_brand LIMIT 10000")


@pytest.mark.parametrize("where", [
    pytest.param("lo_quantity BETWEEN 10 AND 30", id="interval"),
    pytest.param("s_region IN ('ASIA', 'EUROPE')", id="in-list"),
])
def test_fused_kernel_compiles(one_chip, ssb, where):
    _, text = compile_program(one_chip, ssb, _GROUP2.format(where=where),
                               R24, fused="tpu")
    assert "tpu_custom_call" in text


def test_fused_kernel_bf16_limbs_compiles(one_chip, ssb, monkeypatch):
    """The fused kernel with the bf16 limb planes PINOT_TPU_MXU_INT8=0
    selects (Mosaic has no u32 -> bf16 cast; the limbs hop through i32).
    Another row count than the int8 cases, so the jit does not hand back
    their trace."""
    monkeypatch.setattr(mxu_groupby, "_INT8", False)
    monkeypatch.setattr(mxu_groupby, "PLANE_DTYPE", jnp.bfloat16)
    monkeypatch.setattr(mxu_groupby, "LIMB_BITS", 8)
    monkeypatch.setattr(mxu_groupby, "MAX_PLANES", _WIDEST["bfloat16"])
    _, text = compile_program(
        one_chip, ssb, _GROUP2.format(where="lo_quantity BETWEEN 10 AND 30"),
        R22, fused="tpu")
    assert "tpu_custom_call" in text
    assert "bf16" in text and "s8[" not in text


def test_fused_kernel_three_sums_compiles(one_chip, ssb):
    """1 count + 3 x 6 signed-width limb planes: the widest fused shape the
    SSB queries reach."""
    _, text = compile_program(
        one_chip, ssb,
        "SELECT d_year, p_brand, SUM(lo_revenue), SUM(lo_extendedprice), "
        "SUM(lo_quantity) FROM t WHERE lo_discount BETWEEN 1 AND 3 "
        "GROUP BY d_year, p_brand LIMIT 10000", R24, fused="tpu")
    assert "tpu_custom_call" in text


def test_dense_group_by_compiles(one_chip, ssb, monkeypatch):
    """The two-step dense path: XLA mask/gid/limb planes feeding the Pallas
    limb kernel, plus the scatters the MXU cannot do (MIN/MAX, DOUBLE sum,
    DISTINCTCOUNT)."""
    monkeypatch.setattr(mxu_groupby, "backend_platform", lambda: "tpu")
    program, text = compile_program(
        one_chip, ssb,
        "SELECT d_year, SUM(lo_revenue), MIN(lo_revenue), MAX(lo_revenue), "
        "SUM(lo_tax), DISTINCTCOUNT(lo_discount) FROM t "
        "WHERE s_region = 'ASIA' GROUP BY d_year LIMIT 100", R24)
    assert program.mode == "group_by"
    assert "tpu_custom_call" in text


# `dd_distinct_by_year` of the benchmark's `ssb16.drilldown`
_DISTINCT_BY_YEAR = (
    "SELECT d_year, DISTINCTCOUNT(lo_discount), MIN(lo_revenue), "
    "MAX(lo_revenue) FROM t WHERE lo_quantity BETWEEN 12 AND 37 "
    "GROUP BY d_year LIMIT 10")
_MIN_MAX_BY_BRAND = (
    "SELECT p_brand, MIN(lo_revenue), MAX(lo_revenue), MIN(lo_tax) FROM t "
    "WHERE lo_quantity BETWEEN 12 AND 37 GROUP BY p_brand LIMIT 10000")


@pytest.mark.parametrize("sql,groups,label,scatters", [
    pytest.param(_DISTINCT_BY_YEAR, 0,
                 "gby_rng_c0_by1_distinctbitmap_i2_min_c3_max_c3", False,
                 id="dd_distinct_by_year"),
    pytest.param(_MIN_MAX_BY_BRAND, kernels.MINMAX_REDUCE_MAX_GROUPS, None,
                 False, id="at-the-constant"),
    pytest.param(_MIN_MAX_BY_BRAND, kernels.MINMAX_REDUCE_MAX_GROUPS + 1,
                 None, True, id="above-the-constant"),
])
def test_min_max_over_few_groups_compiles_without_a_scatter(
        one_chip, ssb, monkeypatch, sql, groups, label, scatters):
    """A dense group-by's MIN and MAX over a 16 x 2^22-row family: up to
    `MINMAX_REDUCE_MAX_GROUPS` groups they are masked reductions (int32
    and float64 value paths here) with no scatter in the optimised HLO
    (two of 0.6 s a dispatch on the chip for 7 years, PERF.md, PR 32) and
    no loop in its place; one group more keeps the scatters as they were.
    The reduction is ONE broadcast compare whatever the groups: the time
    bound holds it to that (a chain of selects a group would not)."""
    monkeypatch.setattr(mxu_groupby, "backend_platform", lambda: "tpu")
    t0 = time.perf_counter()
    program, text = compile_program(one_chip, ssb, sql, R22, batch=16,
                                     dense_groups=groups)
    took = time.perf_counter() - t0
    print(f"{ir.program_label(program)} x {program.num_groups} groups: "
          f"compiled in {took:.1f} s")
    assert took < 60  # 1-3 s alone; the issue's 10 s is the chip's host
    assert program.mode == "group_by"
    n = sum(agg.kind in ("min", "max") for agg in program.aggs)
    assert kernels.min_max_forms(program) == (
        f"reduce:0,scatter:{n}" if scatters else f"reduce:{n},scatter:0")
    if label:
        assert ir.program_label(program) == label
    assert (_op_count(text, "scatter") > 0) == scatters
    assert _op_count(text, "while") == 0
    assert "tpu_custom_call" in text  # the COUNT column's limb kernel
    # the reductions' fusion is named by a reduction, under the scope the
    # benchmark's `kernel_groupby_ms` reads
    reductions = re.findall(r'op_name="([^"]+/reduce_m(?:in|ax))"', text)
    assert bool(reductions) != scatters
    assert all("/vmap(group_by_dense)/" in n for n in reductions)


def test_sparse_batch_family_compiles(one_chip, ssb):
    """The multi-segment form: one vmapped dispatch over 16 x 2^22 rows."""
    program, _ = compile_program(
        one_chip, ssb,
        "SET sparseGroupBy = true; SELECT lo_orderkey, SUM(lo_revenue), "
        "COUNT(*) FROM t GROUP BY lo_orderkey ORDER BY lo_orderkey "
        "LIMIT 100000", R22, batch=16, sparse_groups=1 << 19)
    assert program.mode == "group_by_sparse"


def test_selection_compiles(one_chip, ssb):
    program, _ = compile_program(
        one_chip, ssb,
        "SELECT lo_orderkey, lo_revenue FROM t WHERE lo_discount = 3 AND "
        "lo_quantity < 5 AND s_region = 'ASIA' LIMIT 50", R24)
    assert program.mode == "selection"


@pytest.mark.parametrize("sql,pallas", [
    pytest.param("SELECT SUM(lo_extendedprice) FROM t WHERE d_year = 1993 "
                 "AND lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25",
                 False, id="filter-sum"),
    pytest.param("SELECT d_year, p_brand, SUM(lo_revenue) FROM t "
                 "WHERE s_region = 'ASIA' GROUP BY d_year, p_brand "
                 "LIMIT 10000", True, id="group-by-2-keys"),
])
def test_batch_family_compiles(one_chip, ssb, monkeypatch, sql, pallas):
    """One vmapped dispatch over a 16 x 2^22-row family (SSB SF10)."""
    monkeypatch.setattr(mxu_groupby, "backend_platform", lambda: "tpu")
    _, text = compile_program(one_chip, ssb, sql, R22, batch=16)
    assert ("tpu_custom_call" in text) == pallas


_Q1_1 = ("SELECT SUM(lo_extendedprice * lo_discount) FROM t WHERE d_year = 1993 "
         "AND lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25")
_Q1_3 = ("SELECT SUM(lo_extendedprice * lo_discount) FROM t WHERE p_brand = 6 "
         "AND d_year = 1994 AND lo_discount BETWEEN 5 AND 7 "
         "AND lo_quantity BETWEEN 26 AND 35")


@pytest.mark.parametrize("sql", [pytest.param(_Q1_1, id="q1.1"),
                                 pytest.param(_Q1_3, id="q1.3")])
def test_dictionary_product_sum_compiles_without_a_gather(one_chip, ssb, sql):
    """SSB flight 1 over a 16 x 2^22-row family: `lo_discount` is
    dictionary-encoded and SUM(lo_extendedprice * lo_discount) reads its
    VALUES. At the family's plane of 16 entries the decode is a select
    chain in the reduction's fusion: no gather (653 of 674.5 ms a dispatch
    on the chip, PERF.md), and none of the loop the compiler expands a
    gather into. A plane of 2 * DICT_SELECT_MAX keeps the gather, as
    before. The time bound guards the cliff: one fusion of 256 selects
    takes the chip's compiler 97 s, which is why the chain is cut into
    fusions of `_DICT_SELECT_FUSE`."""
    t0 = time.perf_counter()
    program, text = compile_program(one_chip, ssb, sql, R22, batch=16,
                                     dict_len=16)
    assert time.perf_counter() - t0 < 60  # about a second alone
    assert program.mode == "aggregation"
    assert len(ir.dict_gathers(program)) == 1
    assert _op_count(text, "gather") == 0 and _op_count(text, "while") == 0
    t0 = time.perf_counter()
    _, text = compile_program(one_chip, ssb, sql, R22, batch=16,
                               dict_len=kernels.DICT_SELECT_MAX)
    assert time.perf_counter() - t0 < 60  # 2 s alone, in fusions of 32
    assert _op_count(text, "gather") == 0
    _, text = compile_program(one_chip, ssb, sql, R22, batch=16,
                               dict_len=2 * kernels.DICT_SELECT_MAX)
    assert _op_count(text, "gather") == 1


# -- the output pack ---------------------------------------------------------


@pytest.mark.parametrize("outs", [
    pytest.param([((16, 7001), "int64"), ((16, 7001), "float64"),
                  ((16, 7001), "uint32")], id="batch-16x7001"),
    pytest.param([((1_600_001,), "int64"), ((1_600_001,), "float64"),
                  ((1_600_000,), "int64")], id="sparse-merged-1600001"),
    # narrow planes beside 64-bit ones: selection bitmaps of a 16 x 2^22
    # family, DISTINCTCOUNT bitmaps, a PTDP block with an odd row count
    pytest.param([((16, 1 << 19), "uint8")], id="selection-16x2^19"),
    pytest.param([((16, 8), "int64"), ((16, 7, 11), "bool"),
                  ((16, 8), "float64")], id="distinct-bitmap"),
    pytest.param([((1_000_003,), "int64"), ((1_000_003,), "bool"),
                  ((1_000_003,), "int16"), ((1_000_003,), "int8")],
                 id="ptdp-odd-rows"),
])
def test_output_pack_compiles_fast(one_chip, outs):
    """Every dispatch ends in kernels._pack_flat (one D2H fetch per query).
    Interleaving words on device (a 64-bit bitcast, a (.., 2) or (.., 4)
    minor dim flattened) cost the chip's compiler 18-145 s for these
    shapes — the broker's default timeout is 60 s; the planar pack takes
    about one. The bound is generous: it guards the cliff, not the
    seconds."""
    specs = tuple(spec(one_chip, shape, jnp.dtype(dt)) for shape, dt in outs)
    t0 = time.perf_counter()
    kernels._pack_flat.lower(specs).compile()
    assert time.perf_counter() - t0 < 60


def test_f64_bitcast_still_unimplemented(one_chip):
    """Why _encode_f64 exists: the TPU compiler's x64 rewrite cannot
    bitcast f64. When this starts compiling, the arithmetic encoding can
    go (ROADMAP queue 3)."""
    planes = spec(one_chip, (16, 7001), jnp.float64)
    with pytest.raises(Exception, match="(?i)x64|unimplemented"):
        jax.jit(lambda x: jax.lax.bitcast_convert_type(
            x, jnp.uint32)).lower(planes).compile()
