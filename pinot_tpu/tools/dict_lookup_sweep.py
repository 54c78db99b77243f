"""Chip sweep behind `ops/kernels.DICT_SELECT_MAX`: SSB Q1.1's program over a
batch family of 16 x 4,194,304 rows, its dictionary plane at several lengths,
decoded by the select chain and by the gather.

    python -m pinot_tpu.tools.dict_lookup_sweep [--planes 16,64,...] [--out f]

One line of JSON a case: plane length, form, selects a fusion, compile
seconds, milliseconds a dispatch (median of `--reps`, host clock around
`block_until_ready`), and whether the SUM equals the gather's. Fails without
a TPU unless `--rehearse` (toy rows, any backend). The forms are forced from
here by setting the two constants of `ops/kernels`; the program has no
option for it.
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

from ..engine.plan import SegmentPlanner
from ..ops import kernels
from ..query.parser.sql import parse_sql
from ..segment.builder import SegmentBuilder
from ..segment.loader import load_segment
from ..spi.data_types import Schema
from ..spi.table_config import IndexingConfig, TableConfig

Q1_1 = ("SELECT SUM(lo_extendedprice * lo_discount) FROM t WHERE d_year = 1993 "
        "AND lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25")
ALWAYS = 1 << 30  # as DICT_SELECT_MAX: every plane; as the fusion's length: no cut


def q1_1_plan(scratch: str):
    """Q1.1 planned against a toy segment (built under `scratch`) with the
    benchmark's encodings."""
    rng = np.random.default_rng(7)
    n = 1 << 13
    schema = Schema.build(
        "t", dimensions=[("d_year", "INT"), ("lo_discount", "INT"),
                         ("lo_quantity", "INT")],
        metrics=[("lo_extendedprice", "INT")])
    cfg = TableConfig(table_name="t", indexing=IndexingConfig(
        no_dictionary_columns=["lo_extendedprice", "lo_quantity"]))
    cols = {"d_year": rng.integers(1992, 1999, n).astype(np.int32),
            "lo_discount": rng.integers(0, 11, n).astype(np.int32),
            "lo_quantity": rng.integers(1, 51, n).astype(np.int32),
            "lo_extendedprice": rng.integers(90_000, 10_495_001, n)
            .astype(np.int32)}
    SegmentBuilder(schema, cfg, "s0").build(cols, scratch + "/s")
    return SegmentPlanner(parse_sql(Q1_1), load_segment(scratch + "/s")).plan()


def family_inputs(plan, segs: int, rows: int, plane: int, rng):
    """Stacked planes of a `segs` x `rows` family as the TPU backend holds
    them: narrow id planes (`packed`), int32 raw planes, a dictionary of
    `plane` entries of which the ids use all."""
    arrays, packed = [], []
    for i, (column, kind) in enumerate(plan.slots):
        if kind == "dict":
            arrays.append(np.tile(np.arange(plane, dtype=np.int32), (segs, 1)))
            continue
        if kind == "ids":
            card = plane if column == "lo_discount" else 7
            width = 8 if card <= 256 else 16
            packed.append((i, width))
            a = rng.integers(0, card, (segs, rows),
                             dtype=np.uint8 if width == 8 else np.uint16)
        elif column == "lo_quantity":
            a = rng.integers(1, 51, (segs, rows), dtype=np.int32)
        else:
            a = rng.integers(90_000, 10_495_001, (segs, rows), dtype=np.int32)
        arrays.append(a)
    params = tuple(np.stack([np.asarray(p)] * segs) for p in plan.params)
    return tuple(jax.device_put(a) for a in arrays), tuple(packed), params


def time_case(plan, arrays, packed, params, rows: int, select_max: int,
              fuse: int, reps: int):
    kernels.DICT_SELECT_MAX = select_max
    kernels._DICT_SELECT_FUSE = fuse
    num_docs = np.full((arrays[0].shape[0],), rows, dtype=np.int32)
    # a jit of its own: the constants are read while tracing, and a cached
    # trace of another case must not answer for this one
    scan = jax.jit(lambda a, p, nd: kernels._run_program_batch(
        plan.program, a, p, nd, rows, packed))

    def call():
        return jax.block_until_ready(scan(arrays, params, num_docs))

    t0 = time.perf_counter()
    outs = call()
    compile_s = time.perf_counter() - t0
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        call()
        ms.append((time.perf_counter() - t0) * 1000)
    return compile_s, statistics.median(ms), [np.asarray(o) for o in outs]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--planes", default="16,64,256,1024,4096")
    ap.add_argument("--fuse", default=f"32,64,{ALWAYS}",
                    help=f"selects a fusion; {ALWAYS} (no cut) is run only "
                         "up to --unfused-max entries")
    ap.add_argument("--unfused-max", type=int, default=64)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"no TPU: found {dev.platform}", file=sys.stderr)
        return 3
    segs, rows = (16, 1 << 22) if not args.rehearse else (2, 1 << 13)
    keep = kernels.DICT_SELECT_MAX, kernels._DICT_SELECT_FUSE
    fuses = [int(x) for x in args.fuse.split(",")]
    with tempfile.TemporaryDirectory(prefix="dict_sweep_") as scratch, \
            open(args.out or os.devnull, "a") as sink:
        plan = q1_1_plan(scratch)
        try:
            for plane in (int(x) for x in args.planes.split(",")):
                arrays, packed, params = family_inputs(
                    plan, segs, rows, plane, np.random.default_rng(plane))
                # (form, DICT_SELECT_MAX, _DICT_SELECT_FUSE, packed)
                cases = [("gather", 0, keep[1], packed)] + [
                    ("select", ALWAYS, f, packed) for f in fuses
                    if f < ALWAYS or plane <= args.unfused_max]
                if plane <= 256:
                    # the chain compares the uint8 ids as stored: the plane
                    # is left out of `packed`, so nothing widens it
                    cases.append(("select-narrow", ALWAYS, keep[1], packed[:1]))
                want = None
                for form, select_max, fuse, packed_c in cases:
                    compile_s, ms, outs = time_case(
                        plan, arrays, packed_c, params, rows, select_max,
                        fuse, args.reps)
                    want = want or outs
                    line = json.dumps({
                        "device": dev.device_kind, "rows": [segs, rows],
                        "plane": plane, "form": form, "fuse": fuse,
                        "compile_s": round(compile_s, 3), "ms": round(ms, 3),
                        "equal": all(np.array_equal(a, b)
                                     for a, b in zip(outs, want))})
                    print(line, flush=True)
                    sink.write(line + "\n")
                    sink.flush()
        finally:
            kernels.DICT_SELECT_MAX, kernels._DICT_SELECT_FUSE = keep
    return 0


if __name__ == "__main__":
    sys.exit(main())
