"""Standing chip check: the served query path on one TPU, at SSB-SF10 size.

    python chip_smoke.py                 one chip (what the driver runs)
    python chip_smoke.py --chips 4       the four-chip mesh path and what it
                                         is compared with, nothing else
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]
                                         same control flow at toy size on
                                         the CPU, Pallas in interpret mode

One process: generates a denormalised SSB ``lineorder`` from ``--seed``,
builds segments, registers them through PropertyStore + ClusterController
+ ServerInstance(backend="tpu") + Broker, sends SQL through
``broker.execute_sql`` and checks every answer against plain NumPy over
the generated columns. It then checks that the device did the work: no
fallback events, the fused and the limb Pallas kernels selected for the
TPU, a dispatch in every cold response and no compile in any warm one.

The times it prints are smoke timings (one cold and one warm reading on a
shared host) — not a benchmark, no rate or utilisation is derived from
them. The last line of stdout is one JSON object; exit code 0 only with
``"ok": true``.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
REGIONS = ["AMERICA", "ASIA", "EUROPE", "AFRICA", "MIDDLE EAST"]
RAW_METRICS = ["lo_extendedprice", "lo_revenue", "lo_quantity"]
# executing queries twice must dispatch twice: keep the broker result
# cache and the segment partial cache out of the way
NOCACHE = "SET resultCache = false; SET segmentCache = false; "
Q1_SQL = ("SELECT SUM(lo_extendedprice) FROM lineorder16 WHERE d_year = 1993 "
          "AND lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25")
Q2_SQL = ("SELECT d_year, p_brand, SUM(lo_revenue) FROM lineorder16 "
          "WHERE s_region = 'ASIA' GROUP BY d_year, p_brand LIMIT 10000")


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- data: schema/cardinalities of the repo's SSB lineorder ------------------


def gen_segment(seed: int, table_id: int, seg: int, n_segs: int, rows: int,
                key_range: int) -> dict:
    """One segment's columns. ``lo_orderkey`` is sorted over the whole
    table (rows arrive in order-key order): segment ``seg`` draws from its
    own slice of the key range."""
    rng = np.random.default_rng([seed, table_id, seg])
    lo, hi = seg * key_range // n_segs, (seg + 1) * key_range // n_segs
    return {
        "d_year": rng.integers(1992, 1999, rows).astype(np.int32),
        "p_brand": rng.integers(0, 1000, rows).astype(np.int32),
        "s_region": rng.integers(0, 5, rows).astype(np.int8),  # code
        "lo_discount": rng.integers(0, 11, rows).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, rows).astype(np.int32),
        "lo_extendedprice": rng.integers(1, 55_001, rows).astype(np.int32),
        "lo_revenue": rng.integers(1, 600_000, rows).astype(np.int32),
        "lo_orderkey": np.sort(rng.integers(lo, hi, rows)).astype(np.int32),
    }


def build_table(name, table_id, n_segs, rows_per_seg, seed, data_dir,
                controller):
    """Generate, build and register one table; returns the concatenated
    columns (the NumPy reference's input)."""
    from pinot_tpu.segment.builder import SegmentBuilder
    from pinot_tpu.spi.data_types import Schema
    from pinot_tpu.spi.table_config import IndexingConfig, TableConfig

    schema = Schema.build(
        name,
        dimensions=[("d_year", "INT"), ("p_brand", "INT"),
                    ("s_region", "STRING"), ("lo_discount", "INT"),
                    ("lo_quantity", "INT"), ("lo_orderkey", "INT")],
        metrics=[("lo_extendedprice", "INT"), ("lo_revenue", "INT")])
    cfg = TableConfig(table_name=name, indexing=IndexingConfig(
        no_dictionary_columns=list(RAW_METRICS)))
    controller.add_schema(schema.to_json())
    table = controller.create_table(cfg.to_json())
    total = n_segs * rows_per_seg
    key_range = max(1 << 22, total // 10)
    region_names = np.asarray(REGIONS, dtype=object)
    parts = []
    for s in range(n_segs):
        cols = gen_segment(seed, table_id, s, n_segs, rows_per_seg,
                           key_range)
        parts.append(cols)
        path = str(Path(data_dir) / name / f"{name}_{s}")
        SegmentBuilder(schema, cfg, f"{name}_{s}").build(
            dict(cols, s_region=region_names[cols["s_region"]]), path)
        controller.add_segment(table, f"{name}_{s}",
                               {"location": path, "numDocs": rows_per_seg})
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


# -- NumPy reference (shares no code with pinot_tpu) -------------------------


def _group_sum(keys, values, n_groups):
    """Exact integer per-group sums. bincount accumulates in float64, which
    is exact while every partial sum stays below 2**53."""
    require(float(values.astype(np.float64).sum()) < 2.0 ** 53,
            "reference sum would leave float64's exact range")
    return np.bincount(keys, weights=values, minlength=n_groups).astype(
        np.int64)


def ref_q1(c):
    m = ((c["d_year"] == 1993) & (c["lo_discount"] >= 1)
         & (c["lo_discount"] <= 3) & (c["lo_quantity"] < 25))
    return [(int(c["lo_extendedprice"][m].astype(np.int64).sum()),)]


def _ref_year_brand(c, m, with_count):
    gid = (c["d_year"][m] - 1992) * 1000 + c["p_brand"][m]
    sums = _group_sum(gid, c["lo_revenue"][m], 7000)
    counts = np.bincount(gid, minlength=7000)
    out = []
    for g in np.flatnonzero(counts):
        row = (1992 + int(g) // 1000, int(g) % 1000, int(sums[g]))
        out.append(row + ((int(counts[g]),) if with_count else ()))
    return out


def ref_q2(c):
    return _ref_year_brand(c, c["s_region"] == REGIONS.index("ASIA"), False)


def ref_fused(c):
    m = (c["lo_quantity"] >= 10) & (c["lo_quantity"] <= 30)
    return _ref_year_brand(c, m, True)


def _first_keys(c, limit):
    """Rows of the ``limit`` smallest order keys (the key column is sorted)."""
    keys = c["lo_orderkey"]
    edges = np.flatnonzero(np.diff(keys)) + 1
    end = int(edges[limit - 1]) if len(edges) >= limit else len(keys)
    uniq, inv = np.unique(keys[:end], return_inverse=True)
    return end, uniq, inv


def ref_highcard(c, limit):
    end, uniq, inv = _first_keys(c, limit)
    sums = _group_sum(inv, c["lo_revenue"][:end], len(uniq))
    counts = np.bincount(inv, minlength=len(uniq))
    return [(int(k), int(s), int(n)) for k, s, n in zip(uniq, sums, counts)]


def ref_highcard_distinct(c, limit):
    end, uniq, inv = _first_keys(c, limit)
    pairs = np.unique(inv.astype(np.int64) * 16 + c["lo_discount"][:end])
    distinct = np.bincount(pairs // 16, minlength=len(uniq))
    sums = _group_sum(inv, c["lo_revenue"][:end], len(uniq))
    return [(int(k), int(d), int(s))
            for k, d, s in zip(uniq, distinct, sums)]


def ref_distinct(c):
    out = []
    for y in range(1992, 1999):
        m = c["d_year"] == y
        if m.any():
            rev = c["lo_revenue"][m]
            out.append((y, len(np.unique(c["lo_discount"][m])),
                        int(rev.min()), int(rev.max())))
    return out


def ref_selection(c, limit):
    m = ((c["lo_discount"] == 3) & (c["lo_quantity"] == 7)
         & (c["d_year"] == 1995))
    rows = sorted(zip(c["lo_orderkey"][m].tolist(),
                      c["lo_revenue"][m].tolist()))
    return [tuple(r) for r in rows[:limit]]


def norm(rows, sort=True):
    """Result rows as tuples of Python ints; a value that is not integral
    fails the comparison instead of being rounded into agreement."""
    out = []
    for r in rows:
        t = []
        for v in r:
            if isinstance(v, (float, np.floating)):
                require(float(v).is_integer(), f"non-integral value {v!r}")
                v = int(v)
            elif isinstance(v, (np.integer,)):
                v = int(v)
            t.append(v)
        out.append(tuple(t))
    return sorted(out) if sort else out


# -- driving the broker ------------------------------------------------------


def watch_compiles() -> dict:
    """Count every XLA executable this process asks for (engine programs,
    output packs, stacking helpers alike), how many came from the
    persistent cache, and the seconds spent compiling or reading them."""
    from jax import monitoring

    seen = {"requests": 0, "hits": 0, "seconds": 0.0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["requests"] += 1
            seen["seconds"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return seen


def run_query(ctx, sql, watchdog_s=50):
    # a query still running close to its timeout (the broker's default is
    # 60 s) is about to fail: say where every thread is (a compile that
    # takes minutes on the chip shows up here, not in any CPU test)
    faulthandler.dump_traceback_later(watchdog_s, exit=False)
    t0 = time.perf_counter()
    try:
        resp = ctx.broker.execute_sql(sql)
    finally:
        faulthandler.cancel_dump_traceback_later()
    rows = resp.result_table.rows if resp.result_table is not None else None
    wall = time.perf_counter() - t0  # rows fetched: device work has ended
    require(not resp.exceptions, f"{sql!r}: {resp.exceptions}")
    require(rows is not None, f"{sql!r}: no result table")
    return resp, rows, wall


def cold_and_warm(ctx, label, sql, expected, ordered=False,
                  one_family=False, watchdog_s=50):
    """Run ``sql`` twice; both answers must equal ``expected``; the first
    must dispatch on the device and the second must not compile. With
    ``one_family`` the table's segments must ride ONE batched dispatch."""
    before = dict(ctx.compiles)
    resp, rows, cold = run_query(ctx, NOCACHE + sql, watchdog_s)
    xla_n = ctx.compiles["requests"] - before["requests"]
    xla_s = ctx.compiles["seconds"] - before["seconds"]
    got = norm(rows, sort=not ordered)
    want = norm(expected, sort=not ordered)
    require(got == want,
            f"{label}: {len(got)} rows differ from the NumPy reference "
            f"({len(want)} rows); first got {got[:2]} want {want[:2]}")
    require(resp.num_device_dispatches >= 1,
            f"{label}: cold run reports no device dispatch")
    require(not one_family or resp.num_device_dispatches == 1,
            f"{label}: {resp.num_device_dispatches} dispatches, want one "
            "batch family")
    cold_stats = (resp.num_device_dispatches, resp.num_compiles)
    resp, rows, warm = run_query(ctx, NOCACHE + sql)
    require(norm(rows, sort=not ordered) == want,
            f"{label}: warm answer differs from the reference")
    require(resp.num_device_dispatches >= 1,
            f"{label}: warm run reports no device dispatch")
    require(resp.num_compiles == 0,
            f"{label}: warm run compiled {resp.num_compiles} program(s)")
    say(f"{label}: {len(want)} rows == numpy | smoke timing cold "
        f"{cold:.3f}s (dispatches {cold_stats[0]}, program compiles "
        f"{cold_stats[1]}; XLA {xla_n} executables in {xla_s:.3f}s) warm "
        f"{warm:.3f}s (dispatches {resp.num_device_dispatches}, compiles 0)")
    return rows


def dispatch_spans(trace_info) -> list:
    """Attributes of every family_dispatch span (traceInfo is a flat list)."""
    return [s.get("attributes") or {} for s in trace_info or []
            if s.get("operator") == "family_dispatch"]


def check_no_fallbacks(ctx):
    from pinot_tpu.engine.perf_ledger import PERF_LEDGER
    from pinot_tpu.ops import fused_groupby

    totals = PERF_LEDGER.snapshot()["fallbackEvents"]["total"]
    # fused-host, mesh-solo, device-join-host, sparse-combine-host: none
    # of any kind may have fired
    require(not any(totals.values()), f"fallback events: {totals}")
    require(fused_groupby._STATE["error"] is None,
            f"fused kernel disabled: {fused_groupby._STATE['error']!r}")
    require(fused_groupby.active() == ctx.kernel_mode,
            f"fused_groupby.active() is {fused_groupby.active()!r}, "
            f"want {ctx.kernel_mode!r}")
    say(f"fallback events total: {totals or '{}'}")


# -- phases ------------------------------------------------------------------


def one_chip_phase(args, ctx):
    from pinot_tpu.ops import mxu_groupby

    rows16 = (1 << 14) if args.rehearse else (1 << 22)
    rows1 = (1 << 14) if args.rehearse else (1 << 24)
    limit = 500 if args.rehearse else 100_000
    t0 = time.perf_counter()
    c16 = build_table("lineorder16", 0, 16, rows16, args.seed, ctx.data_dir,
                      ctx.controller)
    c1 = build_table("lineorder1", 1, 1, rows1, args.seed, ctx.data_dir,
                     ctx.controller)
    say(f"built + registered lineorder16 (16 x {rows16:,} = "
        f"{16 * rows16:,} rows) and lineorder1 (1 x {rows1:,} rows) in "
        f"{time.perf_counter() - t0:.1f}s (host)")
    if not args.rehearse:
        require(mxu_groupby.backend_platform() == "tpu",
                "the limb kernel selector does not see a TPU")

    cold_and_warm(ctx, "q1 filter+SUM, lineorder16 (batch family)", Q1_SQL,
                  ref_q1(c16), one_family=True)
    cold_and_warm(ctx, "q2 dict filter + GROUP BY 2 keys, lineorder16 (batch "
                  "family over the limb kernel)", Q2_SQL, ref_q2(c16),
                  one_family=True)
    fused_sql = ("SELECT d_year, p_brand, SUM(lo_revenue), COUNT(*) FROM "
                 "lineorder1 WHERE lo_quantity BETWEEN 10 AND 30 "
                 "GROUP BY d_year, p_brand LIMIT 10000")
    want_fused = ref_fused(c1)
    cold_and_warm(ctx, "interval filter + GROUP BY + SUM + COUNT, "
                  "lineorder1 (fused kernel)", fused_sql, want_fused)
    resp, rows, _ = run_query(ctx, "SET trace = true; " + NOCACHE + fused_sql)
    require(norm(rows) == norm(want_fused), "traced fused answer differs")
    spans = dispatch_spans(resp.trace_info)
    require(spans, "traced fused query carries no family_dispatch span")
    for a in spans:
        require(a.get("fused") == ctx.kernel_mode
                and not a.get("fusedFallback"),
                f"fused dispatch span says {a.get('fused')!r}, "
                f"fusedFallback={a.get('fusedFallback')!r}")
    say(f"traced fused dispatch span: fused={spans[0].get('fused')!r}, "
        "no fusedFallback")
    # SET sparseGroupBy = true: lineorder1's segment holds more keys than
    # the dense table admits (2^21) and goes sparse by itself, but a
    # lineorder16 segment holds about 419,000 and a rehearsal's far fewer;
    # the option sends all of them down the sort/scan path
    sparse = "SET sparseGroupBy = true; "
    cold_and_warm(
        ctx, "high-cardinality GROUP BY lo_orderkey, lineorder1 "
        "(sparse path)",
        f"{sparse}SET numGroupsLimit = 20000000; SELECT lo_orderkey, "
        f"SUM(lo_revenue), COUNT(*) FROM lineorder1 GROUP BY lo_orderkey "
        f"ORDER BY lo_orderkey LIMIT {limit}", ref_highcard(c1, limit),
        ordered=True)
    # the first cold run compiles a 64-bit lax.sort (the merge of the 16
    # segment tables), which alone takes the chip's compiler most of a
    # minute (cold reading 54 s on the chip's host, 61-74 s of compile in
    # the sandbox): too close to the broker's 60 s default, so this query
    # brings its own timeout
    say("next query sets timeoutMs = 600000: its cold compile (one "
        "lax.sort over int64 keys) takes most of the broker's 60 s default")
    cold_and_warm(
        ctx, "high-cardinality GROUP BY lo_orderkey, lineorder16 (sparse "
        "path, one batch family, 16 segment tables merged on the device)",
        f"{sparse}SET timeoutMs = 600000; SELECT lo_orderkey, "
        f"SUM(lo_revenue), COUNT(*) FROM lineorder16 GROUP BY lo_orderkey "
        f"ORDER BY lo_orderkey LIMIT {limit}", ref_highcard(c16, limit),
        ordered=True, one_family=True, watchdog_s=590)
    cold_and_warm(
        ctx, "DISTINCTCOUNT + SUM inside high-cardinality GROUP BY, "
        "lineorder1 (sparse path)",
        f"{sparse}SET numGroupsLimit = {limit}; SELECT lo_orderkey, "
        f"DISTINCTCOUNT(lo_discount), SUM(lo_revenue) FROM lineorder1 "
        f"GROUP BY lo_orderkey ORDER BY lo_orderkey LIMIT {limit}",
        ref_highcard_distinct(c1, limit), ordered=True)
    cold_and_warm(
        ctx, "DISTINCTCOUNT + MIN + MAX GROUP BY d_year, lineorder16",
        "SELECT d_year, DISTINCTCOUNT(lo_discount), MIN(lo_revenue), "
        "MAX(lo_revenue) FROM lineorder16 GROUP BY d_year LIMIT 100",
        ref_distinct(c16))
    cold_and_warm(
        ctx, "filtered selection ORDER BY LIMIT 50, lineorder16",
        "SELECT lo_orderkey, lo_revenue FROM lineorder16 WHERE "
        "lo_discount = 3 AND lo_quantity = 7 AND d_year = 1995 "
        "ORDER BY lo_orderkey, lo_revenue LIMIT 50",
        ref_selection(c16, 50), ordered=True)
    check_no_fallbacks(ctx)


def four_chip_phase(args, ctx):
    """Batch families sharded over the local devices, against the same
    queries with ``SET meshExecution = false``."""
    import jax

    from pinot_tpu.parallel.mesh import mesh_device_count
    from pinot_tpu.segment.device_cache import GLOBAL_DEVICE_CACHE

    require(mesh_device_count() == 4,
            f"mesh spans {mesh_device_count()} devices, want 4")
    rows16 = (1 << 14) if args.rehearse else (1 << 22)
    t0 = time.perf_counter()
    c16 = build_table("lineorder16", 0, 16, rows16, args.seed, ctx.data_dir,
                      ctx.controller)
    say(f"built + registered lineorder16 (16 x {rows16:,} = "
        f"{16 * rows16:,} rows) in {time.perf_counter() - t0:.1f}s (host)")
    queries = [("q1 filter+SUM", Q1_SQL, ref_q1(c16)),
               ("q2 GROUP BY 2 keys", Q2_SQL, ref_q2(c16))]
    for label, sql, want in queries:
        mesh_rows = cold_and_warm(ctx, f"{label}, mesh over 4 devices",
                                  sql, want, one_family=True)
        solo_rows = cold_and_warm(
            ctx, f"{label}, SET meshExecution = false",
            "SET meshExecution = false; " + sql, want, one_family=True)
        require(sorted(map(tuple, mesh_rows)) == sorted(map(tuple, solo_rows)),
                f"{label}: mesh and solo rows are not bit-identical")
        resp, _, _ = run_query(ctx, "SET trace = true; " + NOCACHE + sql)
        spans = dispatch_spans(resp.trace_info)
        require(any(a.get("meshDevices") == 4 for a in spans),
                f"{label}: no dispatch span reports meshDevices == 4 "
                f"({[a.get('meshDevices') for a in spans]})")
    per_dev = GLOBAL_DEVICE_CACHE.hbm_per_device()
    say(f"resident bytes per device: {per_dev}")
    ids = [d.id for d in jax.devices()]
    require(all(per_dev.get(i, 0) > 0 for i in ids),
            f"a device holds no bytes: {per_dev} over devices {ids}")
    check_no_fallbacks(ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on the CPU (JAX_PLATFORMS=cpu), Pallas "
                         "kernels in interpret mode; never a chip pass")
    args = ap.parse_args(argv)
    device = {"platform": None, "kind": None, "count": 0}
    data_dir = None
    server = None
    ok = False
    try:
        if args.rehearse:
            os.environ["PINOT_TPU_FUSED"] = "interpret"
        import jax

        if not args.rehearse:
            # where JAX_COMPILATION_CACHE_DIR is set JAX already uses it;
            # otherwise one fixed path inside the checkout (the path is
            # part of the cache key, so it must never move)
            if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
                jax.config.update("jax_compilation_cache_dir",
                                  str(ROOT / ".jax_cache_chip"))
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
        compiles = watch_compiles()
        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        say(f"device: {device}, jax {jax.__version__}, seed {args.seed}, "
            f"compile cache at {jax.config.jax_compilation_cache_dir}")
        # a rehearsal is never a chip pass: it runs on the CPU only
        want_platform = "cpu" if args.rehearse else "tpu"
        require(device["platform"] == want_platform,
                f"want platform {want_platform!r}, JAX found "
                f"{device['platform']!r}")
        require(device["count"] == args.chips,
                f"--chips {args.chips} but JAX found {device['count']} "
                "device(s)")

        from pinot_tpu.cluster.broker import Broker
        from pinot_tpu.cluster.controller import ClusterController
        from pinot_tpu.cluster.server import ServerInstance
        from pinot_tpu.cluster.store import PropertyStore
        from pinot_tpu.segment import native_bridge

        say("native host library loaded: "
            f"{native_bridge.get_lib() is not None}")
        data_dir = tempfile.mkdtemp(prefix="chip_smoke_")
        store = PropertyStore()
        controller = ClusterController(store)
        server = ServerInstance(store, "Server_0", backend="tpu")
        server.start()
        ctx = types.SimpleNamespace(
            broker=Broker(store), controller=controller, data_dir=data_dir,
            compiles=compiles,
            kernel_mode="interpret" if args.rehearse else "tpu")
        t0 = time.perf_counter()
        (four_chip_phase if args.chips == 4 else one_chip_phase)(args, ctx)
        say(f"all phases passed in {time.perf_counter() - t0:.1f}s; XLA: "
            f"{compiles['requests']} executables, {compiles['hits']} read "
            f"from the persistent cache, {compiles['seconds']:.2f}s in "
            "backend compile or cache read (smoke timings)")
        ok = True
    except Exception as e:  # the boundary: report, then the verdict line
        import traceback

        traceback.print_exc()
        say(f"FAILED: {type(e).__name__}: {e}")
    finally:
        if server is not None:
            try:
                server.stop()
            except Exception as e:
                say(f"server.stop() failed: {e!r}")
        if data_dir is not None:
            shutil.rmtree(data_dir, ignore_errors=True)
        # written on every way out (an interrupt or SystemExit included,
        # which then propagate): the last line is the verdict
        sys.stderr.flush()
        print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    # daemon threads (rpc accept loops, periodic tasks) must not keep a
    # finished check alive
    code = main()
    sys.stdout.flush()
    os._exit(code)
