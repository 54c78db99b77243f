"""Chip sweep behind `engine/plan.SegmentPlanner._sorted_table_rule`: one integer key's
group-by over a batch family of segments of 4,194,304 rows, its group table
filled by the dense path (above `mxu_groupby.MAX_GROUPS` slots: one 32-bit
scatter per limb) and by the sort-based kernel, at several key counts and
filter factors.

    python -m pinot_tpu.tools.groupby_crossover_sweep [--keys 32768,...]
        [--factors 0.01,0.1,0.25,1.0] [--segs 4] [--out f]

One line of JSON a case: keys, filter factor, form (`dense` or `sorted`),
table slots, compile seconds, milliseconds a SEGMENT (median of `--reps`
dispatches over the family, host clock around `block_until_ready`, divided
by the segments), and whether the sorted table's sums equal the dense
one's. Fails without a TPU unless `--rehearse` (toy rows, any backend).
The forms are made from here by re-sizing one planned Program of each mode;
the program has no option for it.
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
import numpy as np

from ..engine.plan import SegmentPlanner, _key_space_bucket, table_bucket
from ..ops import kernels
from ..query.parser.sql import parse_sql
from ..segment.builder import SegmentBuilder
from ..segment.loader import load_segment
from ..spi.data_types import Schema
from ..spi.table_config import IndexingConfig, TableConfig

SQL = ("SELECT k, SUM(v) FROM t WHERE f BETWEEN 0 AND 9 GROUP BY k "
       "LIMIT 10")
F_RANGE = 1000  # `f` is uniform over [0, F_RANGE): a factor is a range of it


def plans(scratch: str):
    """(dense plan, sorted plan) of SQL against a toy segment: a dictionary
    key, a raw filter column and a raw int32 metric, as the drill-down's
    top-N reads them."""
    rng = np.random.default_rng(7)
    n = 1 << 13
    schema = Schema.build("t", dimensions=[("k", "INT"), ("f", "INT")],
                          metrics=[("v", "INT")])
    cfg = TableConfig(table_name="t", indexing=IndexingConfig(
        no_dictionary_columns=["f", "v"]))
    cols = {"k": rng.integers(0, 4096, n).astype(np.int32),
            "f": rng.integers(0, F_RANGE, n).astype(np.int32),
            "v": rng.integers(1, 10_000_000, n).astype(np.int32)}
    SegmentBuilder(schema, cfg, "s0").build(cols, scratch + "/s")
    seg = load_segment(scratch + "/s")
    dense = SegmentPlanner(parse_sql(SQL), seg).plan()
    sort = SegmentPlanner(
        parse_sql("SET sparseGroupBy = true; " + SQL), seg).plan()
    assert dense.program.mode == "group_by"
    assert sort.program.mode == "group_by_sparse"
    assert dense.slots == sort.slots
    return dense, sort


def family_inputs(plan, segs: int, rows: int, keys: int, factor: float, rng):
    """Stacked int32 planes of a `segs` x `rows` family, every key of
    [0, keys) drawn uniformly, and the filter's bounds for `factor`."""
    arrays = []
    for column, _kind in plan.slots:
        hi = {"k": keys, "f": F_RANGE, "v": 10_000_000}[column]
        arrays.append(rng.integers(0, hi, (segs, rows), dtype=np.int32))
    params = []
    for p in plan.params:
        p = np.asarray(p)
        # the BETWEEN's bounds are the plan's only params: [0, factor)
        v = 0 if int(p) == 0 else max(0, int(round(factor * F_RANGE)) - 1)
        params.append(np.full((segs,), v, dtype=p.dtype))
    return tuple(jax.device_put(a) for a in arrays), tuple(params)


def time_case(program, arrays, params, rows: int, reps: int):
    segs = arrays[0].shape[0]
    num_docs = np.full((segs,), rows, dtype=np.int32)
    scan = jax.jit(lambda a, p, nd: kernels._run_program_batch(
        program, a, p, nd, rows, ()))

    def call():
        return jax.block_until_ready(scan(arrays, params, num_docs))

    t0 = time.perf_counter()
    outs = call()
    compile_s = time.perf_counter() - t0
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        call()
        ms.append((time.perf_counter() - t0) * 1000 / segs)
    return compile_s, statistics.median(ms), outs


def sums_by_key(program, outs, keys: int) -> np.ndarray:
    """(segs, keys) sums of a family's outputs, whichever the form."""
    sums = np.asarray(outs[1])
    if program.mode == "group_by":
        return sums[:, :keys]
    ids = np.asarray(outs[-1])
    dense = np.zeros((sums.shape[0], keys))
    for s in range(sums.shape[0]):
        live = ids[s] >= 0
        dense[s, ids[s][live]] = sums[s, :-1][live]
    return dense


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", default=",".join(
        str(1 << b) for b in (15, 16, 17, 19, 20, 21)))
    ap.add_argument("--factors", default="0.01,0.1,0.25,1.0")
    ap.add_argument("--segs", type=int, default=4)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU: found {dev.platform}", file=sys.stderr)
        return 3
    segs, rows = (args.segs, 1 << 22) if not args.rehearse else (2, 1 << 13)
    factors = [float(x) for x in args.factors.split(",")]
    with tempfile.TemporaryDirectory(prefix="gby_sweep_") as scratch, \
            open(args.out or os.devnull, "a") as sink:
        dense, sort = plans(scratch)
        for keys in (int(x) for x in args.keys.split(",")):
            slots = table_bucket(keys)
            forms = [
                ("dense", dataclasses.replace(dense.program,
                                              num_groups=keys)),
                ("sorted", dataclasses.replace(
                    sort.program, num_groups=slots,
                    key_space=_key_space_bucket(slots)))]
            for factor in factors:
                arrays, params = family_inputs(
                    dense, segs, rows, keys, factor,
                    np.random.default_rng(keys))
                want = None
                for form, program in forms:
                    compile_s, ms, outs = time_case(
                        program, arrays, params, rows, args.reps)
                    got = sums_by_key(program, outs, keys)
                    want = got if want is None else want
                    line = json.dumps({
                        "device": dev.device_kind, "rows": [segs, rows],
                        "keys": keys, "factor": factor, "form": form,
                        "slots": program.num_groups,
                        "compile_s": round(compile_s, 3),
                        "ms_a_segment": round(ms, 3),
                        "equal": bool(np.array_equal(got, want))})
                    print(line, flush=True)
                    sink.write(line + "\n")
                    sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
