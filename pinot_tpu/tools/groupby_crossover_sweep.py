"""Chip sweep behind `engine/plan.SegmentPlanner._sorted_table_rule`: a
group-by by one dictionary key, or by a composite of `--key-columns` of
them, over a batch family of segments of 4,194,304 rows, its group table
filled by the dense path (above `mxu_groupby.MAX_GROUPS` slots: one 32-bit
scatter per limb) and by the sort-based kernel, at several key counts and
filter factors.

    python -m pinot_tpu.tools.groupby_crossover_sweep [--keys 32768,...]
        [--key-columns 1] [--factors 0.01,0.1,0.25,1.0] [--segs 4] [--out f]

One line of JSON a case: keys (with several key columns: the product of
their cardinalities, `cards`), filter factor, form (`dense` or `sorted`),
table slots, the first call's seconds (a form's first factor compiles; the
others run the same executable), milliseconds a SEGMENT (median of `--reps`
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

from ..engine.plan import (SegmentPlanner, _key_space_bucket,
                           row_major_strides, table_bucket)
from ..ops import kernels
from ..query.parser.sql import parse_sql
from ..segment.builder import SegmentBuilder
from ..segment.loader import load_segment
from ..spi.data_types import Schema
from ..spi.table_config import IndexingConfig, TableConfig

F_RANGE = 1000  # `f` is uniform over [0, F_RANGE): a factor is a range of it
TOY_CARD = 8  # a toy key column's entries: three of them stay a limb table


def plans(scratch: str, key_columns: int = 1):
    """(dense plan, sorted plan) of a filtered SUM grouped by `key_columns`
    dictionary keys against a toy segment: the keys, a raw filter column
    and a raw int32 metric, as the drill-down's top-N and flight 3's
    city-to-city tables read them."""
    rng = np.random.default_rng(7)
    n = 1 << 13
    keys = [f"k{i}" for i in range(key_columns)]
    schema = Schema.build(
        "t", dimensions=[(k, "INT") for k in keys] + [("f", "INT")],
        metrics=[("v", "INT")])
    cfg = TableConfig(table_name="t", indexing=IndexingConfig(
        no_dictionary_columns=["f", "v"]))
    cols = {k: rng.integers(0, TOY_CARD, n).astype(np.int32) for k in keys}
    cols["f"] = rng.integers(0, F_RANGE, n).astype(np.int32)
    cols["v"] = rng.integers(1, 10_000_000, n).astype(np.int32)
    SegmentBuilder(schema, cfg, "s0").build(cols, scratch + "/s")
    seg = load_segment(scratch + "/s")
    by = ", ".join(keys)
    sql = (f"SELECT {by}, SUM(v) FROM t WHERE f BETWEEN 0 AND 9 "
           f"GROUP BY {by} LIMIT 10")
    dense = SegmentPlanner(parse_sql(sql), seg).plan()
    sort = SegmentPlanner(
        parse_sql("SET sparseGroupBy = true; " + sql), seg).plan()
    assert dense.program.mode == "group_by"
    assert sort.program.mode == "group_by_sparse"
    assert dense.slots == sort.slots
    return dense, sort


def key_cards(keys: int, key_columns: int) -> list:
    """`key_columns` cardinalities whose product is `keys`, as near to one
    another as its divisors allow (458,752 over three: 64 x 64 x 112)."""
    cards, left = [], keys
    for i in range(key_columns, 1, -1):
        root = round(left ** (1 / i))
        card = next(c for c in range(root, 0, -1) if left % c == 0)
        cards.append(card)
        left //= card
    return cards + [left]


def sized(program, cards: list, slots: int):
    """`program` re-sized to key columns of `cards` entries and a table of
    `slots` slots."""
    size = dict(group_strides=tuple(row_major_strides(cards)),
                num_groups=slots)
    if program.mode == "group_by_sparse":
        size["key_space"] = _key_space_bucket(slots)
    return dataclasses.replace(program, **size)


def family_inputs(plan, segs: int, rows: int, cards: list, factor: float,
                  rng):
    """Stacked int32 planes of a `segs` x `rows` family, every entry of
    every key column drawn uniformly, and the filter's bounds for
    `factor`."""
    his = {f"k{i}": card for i, card in enumerate(cards)}
    his.update(f=F_RANGE, v=10_000_000)
    arrays = []
    for column, _kind in plan.slots:
        arrays.append(rng.integers(0, his[column], (segs, rows),
                                   dtype=np.int32))
    params = []
    for p in plan.params:
        p = np.asarray(p)
        # the BETWEEN's bounds are the plan's only params: [0, factor)
        v = 0 if int(p) == 0 else max(0, int(round(factor * F_RANGE)) - 1)
        params.append(np.full((segs,), v, dtype=p.dtype))
    return tuple(jax.device_put(a) for a in arrays), tuple(params)


def scan_of(program, rows: int):
    """The family's scan as one jitted function: compiled at its first
    call, the same executable at every filter factor (the bounds are
    params)."""
    return jax.jit(lambda a, p, nd: kernels._run_program_batch(
        program, a, p, nd, rows, ()))


def time_case(scan, arrays, params, rows: int, reps: int):
    """(first call's seconds, median ms a segment, outputs): the first call
    compiles where `scan` has not run yet."""
    segs = arrays[0].shape[0]
    num_docs = np.full((segs,), rows, dtype=np.int32)

    def call():
        return jax.block_until_ready(scan(arrays, params, num_docs))

    t0 = time.perf_counter()
    outs = call()
    first_s = time.perf_counter() - t0
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        call()
        ms.append((time.perf_counter() - t0) * 1000 / segs)
    return first_s, statistics.median(ms), outs


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
    ap.add_argument("--key-columns", type=int, default=1)
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
        dense, sort = plans(scratch, args.key_columns)
        for keys in (int(x) for x in args.keys.split(",")):
            cards = key_cards(keys, args.key_columns)
            forms = [(form, program, scan_of(program, rows))
                     for form, program in (
                         ("dense", sized(dense.program, cards, keys)),
                         ("sorted", sized(sort.program, cards,
                                          table_bucket(keys))))]
            for factor in factors:
                arrays, params = family_inputs(
                    dense, segs, rows, cards, factor,
                    np.random.default_rng(keys))
                want = None
                for form, program, scan in forms:
                    first_s, ms, outs = time_case(
                        scan, arrays, params, rows, args.reps)
                    got = sums_by_key(program, outs, keys)
                    want = got if want is None else want
                    line = json.dumps({
                        "device": dev.device_kind, "rows": [segs, rows],
                        "keys": keys, "cards": cards, "factor": factor,
                        "form": form,
                        "slots": program.num_groups,
                        "first_call_s": round(first_s, 3),
                        "ms_a_segment": round(ms, 3),
                        "equal": bool(np.array_equal(got, want))})
                    print(line, flush=True)
                    sink.write(line + "\n")
                    sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
