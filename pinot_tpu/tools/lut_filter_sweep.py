"""Chip sweep behind `ops/kernels.LUT_BITS_MAX`: SSB Q3.3's filter (two LUT
filters, `c_city IN (..)` and `s_city IN (..)`, and a `d_year` range) over a
batch family of 16 x 4,194,304 rows, as the batch path runs it before the
sorted table's loop, its two LUTs at several lengths, lowered as a bit test
and as a gather.

    python -m pinot_tpu.tools.lut_filter_sweep [--luts 16,256,...] [--out f]

One line of JSON a case: LUT length, its uint32 words, form, compile
seconds, milliseconds a dispatch (median of `--reps`, host clock around
`block_until_ready`), and whether the row masks equal the gather's. The
sorted table after the filter is the same program whatever the form, and
costs the chip's compiler half a minute, so it is left out. Fails without
a TPU unless `--rehearse` (toy rows, any backend). The form is forced from
here by setting `kernels.LUT_BITS_MAX`; the program has no option for it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

import jax
import numpy as np

from ..engine import ir
from ..engine.plan import SegmentPlanner
from ..ops import kernels
from ..query.parser.sql import parse_sql
from ..segment.builder import SegmentBuilder
from ..segment.loader import load_segment
from ..spi.data_types import Schema
from ..spi.table_config import IndexingConfig, TableConfig

Q3_3 = ("SELECT SUM(lo_revenue), c_city, s_city, d_year FROM t WHERE "
        "c_city IN ('NATION0191', 'NATION0195') AND s_city IN "
        "('NATION0191', 'NATION0195') AND d_year BETWEEN 1992 AND 1997 "
        "GROUP BY c_city, s_city, d_year ORDER BY d_year ASC, "
        "SUM(lo_revenue) DESC, c_city, s_city LIMIT 25")
ALWAYS = 1 << 30  # as LUT_BITS_MAX: every LUT takes the bit test


def q3_3_plan(scratch: str):
    """Q3.3 planned against a toy segment (built under `scratch`) with the
    cell's encodings: 250 cities a side as strings, 7 years."""
    n = 1 << 13
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
    SegmentBuilder(schema, cfg, "s0").build(cols, scratch + "/s")
    plan = SegmentPlanner(parse_sql(Q3_3), load_segment(scratch + "/s")).plan()
    assert len(ir.luts(plan.program)) == 2, plan.program
    return plan


def family_inputs(plan, segs: int, rows: int, lut_len: int, rng):
    """Stacked planes and params of a `segs` x `rows` family: each LUT of
    `lut_len` entries (a dictionary of `lut_len - 1` values and the pad
    entry) with two values set, its ids drawn over the dictionary, as
    narrow planes (`packed`) the way the TPU backend holds them."""
    program = plan.program
    lut_slot = {node.ids_slot: node.lut_param
                for node in ir.luts(program)}
    arrays, packed = [], []
    for i, (_column, kind) in enumerate(plan.slots):
        if kind == "ids":
            card = lut_len - 1 if i in lut_slot else 7
            width = 8 if card <= 256 else 16
            packed.append((i, width))
            arrays.append(rng.integers(
                0, max(card, 1), (segs, rows),
                dtype=np.uint8 if width == 8 else np.uint16))
        else:
            arrays.append(rng.integers(1, 600_000, (segs, rows),
                                       dtype=np.int32))
    params = []
    for idx, p in enumerate(plan.params):
        p = np.asarray(p)
        if idx in lut_slot.values():
            p = np.zeros(lut_len, dtype=bool)
            p[rng.choice(max(lut_len - 1, 1), min(2, lut_len), False)] = True
            p[-1] = False
        params.append(np.stack([p] * segs))
    return (tuple(jax.device_put(a) for a in arrays), tuple(packed),
            tuple(jax.device_put(p) for p in params))


def time_case(program, arrays, packed, params, rows: int, bits_max: int,
              reps: int):
    kernels.LUT_BITS_MAX = bits_max
    num_docs = np.full((arrays[0].shape[0],), rows, dtype=np.int32)

    # the filter as `_run_program_batch` runs it for the sorted table: the
    # planes widened, then one vmap of `_filter_mask` over the members. A
    # jit of its own: the constant is read while tracing
    @jax.jit
    def masks(a, p, nd):
        a = kernels._apply_packed(a, packed)
        return jax.vmap(lambda a_s, p_s, n: kernels._filter_mask(
            program, a_s, p_s, n, rows))(a, p, nd)

    def call():
        return jax.block_until_ready(masks(arrays, params, num_docs))

    t0 = time.perf_counter()
    out = call()
    compile_s = time.perf_counter() - t0
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        call()
        ms.append((time.perf_counter() - t0) * 1000)
    return compile_s, statistics.median(ms), np.asarray(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--luts", default="16,64,251,1024,2048,4096,8192")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU: found {dev.platform}", file=sys.stderr)
        return 3
    segs, rows = (16, 1 << 22) if not args.rehearse else (2, 1 << 13)
    keep = kernels.LUT_BITS_MAX
    with tempfile.TemporaryDirectory(prefix="lut_sweep_") as scratch, \
            open(args.out or os.devnull, "a") as sink:
        plan = q3_3_plan(scratch)
        try:
            for lut_len in (int(x) for x in args.luts.split(",")):
                arrays, packed, params = family_inputs(
                    plan, segs, rows, lut_len,
                    np.random.default_rng(lut_len))
                want = None
                for form, bits_max in (("gather", 0), ("bits", ALWAYS)):
                    compile_s, ms, out = time_case(
                        plan.program, arrays, packed, params, rows, bits_max,
                        args.reps)
                    want = out if want is None else want
                    line = json.dumps({
                        "device": dev.device_kind, "rows": [segs, rows],
                        "lut": lut_len, "words": -(-lut_len // 32),
                        "form": form, "compile_s": round(compile_s, 3),
                        "ms": round(ms, 3),
                        "rows_passing": int(out.sum()),
                        "equal": bool(np.array_equal(out, want))})
                    print(line, flush=True)
                    sink.write(line + "\n")
                    sink.flush()
        finally:
            kernels.LUT_BITS_MAX = keep
    return 0


if __name__ == "__main__":
    sys.exit(main())
