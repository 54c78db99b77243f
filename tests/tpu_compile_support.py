"""What the compile tests (tests/test_tpu_compile*.py) share: the described
chip, the small SSB-shaped segment plans are made from, and the helpers
that scale a plan's shapes to a real segment and compile it. The fixtures
reach the test files through conftest.py.

The TPU compiler is installed beside JAX and compiles for a topology that
is described, not attached. The topology is described inside a fixture —
never at import — so every pytest-xdist worker collects the same tests.
Without ALLOW_MULTIPLE_LIBTPU_LOAD=1 only one process at a time may load the
TPU library (a second aborts on /tmp/libtpu_lockfile): a run with no xdist
is one process and needs nothing; with the variable (conftest.py sets it
for the tests, the driver's command too) any number of workers describe the
chip side by side, which is what lets the compile cases live in several
files that `--dist loadfile` hands to several workers.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from pinot_tpu.engine.plan import SegmentPlanner
from pinot_tpu.ops import fused_groupby, kernels
from pinot_tpu.query.parser.sql import parse_sql
from pinot_tpu.segment.builder import SegmentBuilder
from pinot_tpu.segment.device_cache import SegmentDeviceView
from pinot_tpu.segment.loader import load_segment
from pinot_tpu.spi.data_types import Schema
from pinot_tpu.spi.table_config import IndexingConfig, TableConfig

R20, R22, R24 = 1 << 20, 1 << 22, 1 << 24


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep these out of it
    prev = jax.config.jax_enable_compilation_cache
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


@pytest.fixture(scope="module")
def ssb(tmp_path_factory):
    """A small SSB-shaped segment: plans come from it, shapes are then
    scaled to the real row counts (a compile needs shapes, not data)."""
    rng = np.random.default_rng(7)
    n = 1 << 15
    schema = Schema.build(
        "t",
        dimensions=[("d_year", "INT"), ("p_brand", "INT"),
                    ("s_region", "STRING"), ("lo_discount", "INT"),
                    ("lo_quantity", "INT"), ("lo_orderkey", "INT")],
        metrics=[("lo_extendedprice", "INT"), ("lo_revenue", "INT"),
                 ("lo_tax", "DOUBLE")])
    cfg = TableConfig(table_name="t", indexing=IndexingConfig(
        no_dictionary_columns=["lo_extendedprice", "lo_revenue",
                               "lo_quantity", "lo_tax"]))
    regions = np.asarray(["AMERICA", "ASIA", "EUROPE", "AFRICA",
                          "MIDDLE EAST"], dtype=object)
    cols = {
        "d_year": rng.integers(1992, 1999, n).astype(np.int32),
        "p_brand": rng.integers(0, 1000, n).astype(np.int32),
        "s_region": regions[rng.integers(0, 5, n)],
        "lo_discount": rng.integers(0, 11, n).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, n).astype(np.int32),
        "lo_orderkey": np.sort(rng.integers(0, n // 4, n)).astype(np.int32),
        "lo_extendedprice": rng.integers(1, 55_001, n).astype(np.int32),
        "lo_revenue": rng.integers(1, 600_000, n).astype(np.int32),
        "lo_tax": rng.random(n) * 8,
    }
    path = str(tmp_path_factory.mktemp("tpu_compile") / "s")
    SegmentBuilder(schema, cfg, "s0").build(cols, path)
    segment = load_segment(path)
    return segment, SegmentDeviceView(segment)


def spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)


def compile_program(one_chip, ssb, sql, padded, *, batch=0, fused="",
                     sparse_groups=0, dict_len=0, whole_table=False,
                     dense_groups=0):
    """Plan ``sql`` against the small segment, then lower run_program /
    run_program_batch with every row plane scaled to ``padded`` rows (and
    an [S] batch dim when ``batch``). ``sparse_groups`` scales a sparse
    program's key space, output groups and dictionary plane to a real
    high-cardinality segment (cut at numGroupsLimit's default, or under
    ``whole_table`` with a slot for every key, as the planner sizes a table
    it sorts by its own rule); ``dict_len`` sets the dictionary planes'
    length (a family's `executor._dict_pad` bucket); ``dense_groups``
    re-sizes a dense program's table."""
    segment, view = ssb
    plan = SegmentPlanner(parse_sql(sql), segment).plan()
    arrays, packed = plan.gather_arrays_packed(view)
    params = tuple(np.asarray(p) for p in plan.params)
    program = plan.program
    lut_meta = ()
    if fused:
        extra, lut_meta = fused_groupby.lut_run_params(program, params)
        assert fused_groupby.plan(program, arrays, lut_meta) is not None
        params += extra
    if sparse_groups:
        assert program.mode == "group_by_sparse"
        program = dataclasses.replace(
            program, key_space=sparse_groups,
            num_groups=sparse_groups if whole_table
            else min(sparse_groups, 100_000))
    if dense_groups:
        assert program.mode == "group_by"
        program = dataclasses.replace(program, num_groups=dense_groups)
    lead = [batch] if batch else []

    def plane(a, kind):
        shape = list(a.shape)
        if kind == "dict":
            if sparse_groups or dict_len:
                shape[0] = sparse_groups or dict_len
        else:
            assert shape[0] == view.padded
            shape[0] = padded
        return spec(one_chip, lead + shape, a.dtype)

    a_s = tuple(plane(a, kind) for a, (_c, kind) in zip(arrays, plan.slots))
    p_s = tuple(spec(one_chip, lead + list(p.shape), p.dtype)
                for p in params)
    if batch:
        lowered = kernels.run_program_batch.lower(
            program, a_s, p_s, spec(one_chip, (batch,), jnp.int32),
            padded=padded, packed=packed)
    else:
        lowered = kernels.run_program.lower(
            program, a_s, p_s, spec(one_chip, (), jnp.int32), padded=padded,
            packed=packed, fused=fused, fused_lut_meta=lut_meta)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    assert resident < 16 * 10 ** 9, f"{resident} bytes do not fit one v5e"
    return program, compiled.as_text()


# -- the device merge (kernels.merge_group_tables) ---------------------------

# by case id; one test function a file takes the cases that file holds
# (`merge_cases`), so the heavy ones compile on different workers
MERGE_CASES = {
    # dd_top_orders at the issue's size: 16 tables of 2^20 slots (1.05
    # million orders a segment, consecutive integers), two sums, cut to
    # 5,000 by SUM DESC, then the key
    "cut-16x2^20": dict(
        keys=1 << 20, how="base", key32=True,
        states=(jnp.float64, jnp.float64), kinds=("add", "add"),
        order=(1, True, False), cut=1 << 13, table=0, seconds=300,
        temp_bytes=4e9),
    # dd_top_customers: 16 tables of 9 * 2^15 slots, never cut (300,000
    # customers are under the threshold): the whole merged table crosses;
    # a min and a max beside the sum (the count column rides every merge)
    "whole-16x294912": dict(
        keys=9 << 15, how="plane", key32=True,
        states=(jnp.float64, jnp.float64, jnp.float64),
        kinds=("add", "min", "max"), order=None, cut=0, table=19 << 14,
        seconds=600, temp_bytes=4e9),
    # what `SET sparseGroupBy` and tables above 2^21 keys still send: 16
    # tables cut at numGroupsLimit's 100,000 slots, int64 keys in value
    # space already (tables kept on the device), merged whole (no branch
    # at this size) into a table for every slot; a sum and a count (every
    # 64-bit column the sort carries costs the compiler a minute here: the
    # case above has the four kinds)
    "values-16x100000": dict(
        keys=100_000, how="values", key32=False,
        states=(jnp.float64, jnp.int64), kinds=("add", "add"), order=None,
        cut=0, table=1 << 21, seconds=400, temp_bytes=4e9),
}


def merge_cases(*ids):
    return [pytest.param(MERGE_CASES[i], id=i) for i in ids]


def check_device_merge_compiles(one_chip, *, keys, how, key32, states, kinds,
                                order, cut, table, seconds, temp_bytes):
    """The server-level merge of 16 segments' sparse tables, cut on the
    device (kernels.merge_group_tables): dictionary ids to values, one sort
    that carries the columns, shift-pass scans, the bisection for the k-th
    value and the f64 -> int64 ranking, at the drill-down's sizes, both
    sides of the branch on how full the tables are. Run on the chip's
    machine (its host compiles; PR 30): 52.7 s, 125.5 s and 128.0 s (the
    last two with four state columns each; three and two here), and 1.79
    GB, 0.60 GB and 0.09 GB of temporaries; the sandbox's host takes 76 s,
    then 244-278 and 300-526 with four columns. The time follows the
    sorts and the 64-bit columns they carry, not the sizes: 2 tables of
    131,072 and 147,456 slots compiled in 68 and 155 s here (PR 31), the
    cases at full size in 86 and 182 s. The seconds allowed are for the
    sandbox under six workers."""
    s = 16
    source = {"base": spec(one_chip, (s,), jnp.int64),
              "plane": spec(one_chip, (s, keys), jnp.int32),
              "values": None}[how]
    tables = ((spec(one_chip, (s, keys), jnp.int64), source,
               spec(one_chip, (s, keys + 1), jnp.int64),
               tuple(spec(one_chip, (s, keys + 1), dt) for dt in states)),)
    t0 = time.perf_counter()
    compiled = kernels.merge_group_tables.lower(
        tables, spec(one_chip, (), jnp.int64),
        spec(one_chip, (), jnp.int64), how=(how,), key32=key32, kinds=kinds,
        order=order, cut_slots=cut, table_slots=table).compile()
    took = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    print(f"merge_group_tables {keys} x {s} ({how}): compiled in {took:.1f} s, "
          f"temp {mem.temp_size_in_bytes} bytes")
    assert took < seconds
    assert mem.temp_size_in_bytes < temp_bytes
