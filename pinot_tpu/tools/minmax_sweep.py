"""Chip sweep behind `ops/kernels.MINMAX_REDUCE_MAX_GROUPS`: a dense
group-by's MIN and MAX over a batch family of 16 segments of 4,194,304 rows,
taken by the masked reduction and by the scatter, at several table sizes,
filter factors and value types.

    python -m pinot_tpu.tools.minmax_sweep [--groups 1,8,32,128,512,2048]
        [--factors 0.01,0.5] [--types INT,FLOAT,DOUBLE] [--segs 16]
        [--firsts 3] [--reps 3] [--out f]

One line of JSON a case: value type, groups, filter factor, form (`scatter`
or `reduce`), seconds of the first use (trace + compile + one run, the
persistent compile cache off; median of `--firsts`, each a jit of its own),
milliseconds a SEGMENT (median of `--reps` dispatches over the family, host
clock around `block_until_ready`, divided by the segments), and whether the
outputs equal the scatter's bit for bit. Fails without a TPU unless
`--rehearse` (toy rows, any backend). The whole program is timed (the COUNT
column's limb pass is in both forms). The forms are forced from here by
setting the constant of `ops/kernels` around the trace; the program has no
option for it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.plan import SegmentPlanner
from ..ops import kernels
from ..query.parser.sql import parse_sql
from ..segment.builder import SegmentBuilder
from ..segment.loader import load_segment
from ..spi.data_types import Schema
from ..spi.table_config import IndexingConfig, TableConfig

SQL = ("SELECT k, MIN(v), MAX(v) FROM t WHERE f BETWEEN 0 AND 9 GROUP BY k "
       "LIMIT 10")
F_RANGE = 1000  # `f` is uniform over [0, F_RANGE): a factor is a range of it
FORMS = {"scatter": 0, "reduce": 1 << 30}  # MINMAX_REDUCE_MAX_GROUPS to force
VALUE_DTYPES = {"INT": jnp.int32, "FLOAT": jnp.float32, "DOUBLE": jnp.float64}


def plan_of(scratch: str, vtype: str):
    """SQL planned against a toy segment: a dictionary key, a raw filter
    column and a raw metric of `vtype`, as `dd_distinct_by_year` reads
    `d_year`, `lo_quantity` and `lo_revenue`."""
    rng = np.random.default_rng(7)
    n = 1 << 13
    schema = Schema.build("t", dimensions=[("k", "INT"), ("f", "INT")],
                          metrics=[("v", vtype)])
    cfg = TableConfig(table_name="t", indexing=IndexingConfig(
        no_dictionary_columns=["f", "v"]))
    v = rng.integers(1, 10_000_000, n)
    cols = {"k": rng.integers(0, 4096, n).astype(np.int32),
            "f": rng.integers(0, F_RANGE, n).astype(np.int32),
            "v": v.astype(np.int32) if vtype == "INT" else
            (v / 7).astype(np.float32 if vtype == "FLOAT" else np.float64)}
    path = f"{scratch}/{vtype}"
    SegmentBuilder(schema, cfg, "s0").build(cols, path)
    plan = SegmentPlanner(parse_sql(SQL), load_segment(path)).plan()
    assert plan.program.mode == "group_by"
    assert [a.kind for a in plan.program.aggs] == ["min", "max"]
    return plan


def family_inputs(plan, vtype: str, segs: int, rows: int, groups: int,
                  factor: float, seed: int):
    """Stacked planes of a `segs` x `rows` family, made on the device: every
    key of [0, groups) drawn uniformly, and the filter's bounds for
    `factor`."""
    keys = jax.random.split(jax.random.key(seed), len(plan.slots))
    arrays = []
    for key, (column, _kind) in zip(keys, plan.slots):
        hi = {"k": groups, "f": F_RANGE, "v": 10_000_000}[column]
        a = jax.random.randint(key, (segs, rows), 0, hi, dtype=jnp.int32)
        if column == "v" and vtype != "INT":
            a = (a / 7).astype(VALUE_DTYPES[vtype])
        arrays.append(a)
    params = []
    for p in plan.params:
        p = np.asarray(p)
        # the BETWEEN's bounds are the plan's only params: [0, factor)
        v = 0 if int(p) == 0 else max(0, int(round(factor * F_RANGE)) - 1)
        params.append(np.full((segs,), v, dtype=p.dtype))
    return tuple(jax.block_until_ready(a) for a in arrays), tuple(params)


def time_case(program, arrays, params, rows: int, reps: int, firsts: int):
    """(median seconds of a first use, median ms a segment, outputs): a
    first use traces, compiles and runs a jit of its own, `firsts` times."""
    segs = arrays[0].shape[0]
    num_docs = np.full((segs,), rows, dtype=np.int32)
    first_s = []
    for _ in range(firsts):
        scan = jax.jit(lambda a, p, nd: kernels._run_program_batch(
            program, a, p, nd, rows, ()))
        t0 = time.perf_counter()
        outs = jax.block_until_ready(scan(arrays, params, num_docs))
        first_s.append(time.perf_counter() - t0)
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(scan(arrays, params, num_docs))
        ms.append((time.perf_counter() - t0) * 1000 / segs)
    return statistics.median(first_s), statistics.median(ms), outs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", default="1,8,32,128,512,2048")
    ap.add_argument("--factors", default="0.01,0.5")
    ap.add_argument("--types", default="INT,FLOAT")
    ap.add_argument("--segs", type=int, default=16)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--firsts", type=int, default=3)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU: found {dev.platform}", file=sys.stderr)
        return 3
    segs, rows = (args.segs, 1 << 22) if not args.rehearse else (2, 1 << 13)
    # a first use compiles: nothing is read from the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    kept = kernels.MINMAX_REDUCE_MAX_GROUPS
    with tempfile.TemporaryDirectory(prefix="minmax_sweep_") as scratch, \
            open(args.out or os.devnull, "a") as sink:
        try:
            for vtype in args.types.split(","):
                plan = plan_of(scratch, vtype)
                for groups in (int(x) for x in args.groups.split(",")):
                    program = dataclasses.replace(plan.program,
                                                  num_groups=groups)
                    for factor in (float(x) for x in args.factors.split(",")):
                        arrays, params = family_inputs(
                            plan, vtype, segs, rows, groups, factor,
                            seed=groups)
                        want = None
                        for form, constant in FORMS.items():
                            kernels.MINMAX_REDUCE_MAX_GROUPS = constant
                            assert kernels.min_max_form(groups) == form
                            first_s, ms, outs = time_case(
                                program, arrays, params, rows, args.reps,
                                args.firsts)
                            got = [np.asarray(o).tobytes() for o in outs]
                            want = want or got
                            line = json.dumps({
                                "device": dev.device_kind,
                                "rows": [segs, rows], "type": vtype,
                                "groups": groups, "factor": factor,
                                "form": form, "first_use_s": round(first_s, 3),
                                "ms_a_segment": round(ms, 3),
                                "equal": got == want})
                            print(line, flush=True)
                            sink.write(line + "\n")
                            sink.flush()
                        del arrays
        finally:
            kernels.MINMAX_REDUCE_MAX_GROUPS = kept
    return 0


if __name__ == "__main__":
    sys.exit(main())
