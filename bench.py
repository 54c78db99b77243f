"""Benchmark: BASELINE.json configs on one chip.

Configs (BASELINE.md, scaled to BENCH_ROWS total rows each):
  q1  SSB Q1.1-style range filter + SUM           (1 segment)
  q2  SSB Q2-style dict filter + GROUP BY 2 dims  (1 segment)   ← headline
  q3  high-cardinality GROUP BY (sparse device path)
  q4  16-segment combine of q2 (batched async dispatch)
  q5  NYC-Taxi-style COUNT DISTINCT + PERCENTILE_TDIGEST GROUP BY day
  q6  sparse COUNT DISTINCT inside a high-card group-by
  q7  LOOKUP star join    q8  MSE equi-join    q9  3-SUM group-by
  q9j MSE LEFT join (residual ON filter)   q10  MSE 2-join chain

Architecture:
  * The PARENT process never touches JAX: a chip belongs to one process at
    a time, and a parent that held it would starve the children. It
    builds/caches segments on the host, then runs each config in its OWN
    subprocess (`bench.py --config qN --out FILE`), one at a time.
  * Without BENCH_PLATFORM=cpu given by the caller, a child that finds no
    TPU exits non-zero: there is no fallback to the CPU.
  * Each child enforces an INTERNAL deadline (checked between iterations)
    and exits cleanly; a child that outlives its deadline + grace is
    killed and the remaining configs are skipped.
  * The parent RE-PRINTS the full summary JSON line after every config
    completes (flushing stdout), so even if the driver times the bench out,
    the last parseable line carries every config that finished. Partials
    also land in BENCH_PARTIAL_DIR (default .bench_partial/, untracked).

The CPU baseline is this repo's host (numpy) engine on the same machine
(the reference publishes no absolute numbers — BASELINE.md). Roofline:
bytes/s is the column-plane bytes each query must read from HBM divided
by p50, reported against the v5e peak of ~819 GB/s.

Prints ONE JSON line (repeatedly, updated as configs finish):
  {"metric": ..., "value": rows/sec/chip, "unit": "rows/s", "vs_baseline": x}

Env knobs: BENCH_ROWS (default 100M), BENCH_ITERS (default 10),
BENCH_PLATFORM (e.g. cpu for local runs), BENCH_CONFIGS (csv, default all),
BENCH_TIME_BUDGET_S (default 2040 — below the driver's external timeout so
the parent always gets to emit).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time
from pathlib import Path

import numpy as np

ROWS = int(os.environ.get("BENCH_ROWS", 100_000_000))
ITERS = int(os.environ.get("BENCH_ITERS", 10))
TIME_BUDGET_S = float(os.environ.get("BENCH_TIME_BUDGET_S", 2040))
_START = time.monotonic()
# q6 runs LAST: its sparse-distinct program has the slowest cold compile,
# and a hung/abandoned child skips every config after it
CONFIGS = [c for c in os.environ.get(
    "BENCH_CONFIGS",
    "q1,q2,q9,q3,q4,q5,q7,q8,q9j,q10,q3m,q6m,q11r,q6").split(",") if c]
ROOT = Path(__file__).parent
CACHE = ROOT / ".bench_cache"
# per-config results and the running summary land here (untracked)
PARTIAL = Path(os.environ.get("BENCH_PARTIAL_DIR", ROOT / ".bench_partial"))
V5E_HBM_PEAK = 819e9  # bytes/s

Q1 = ("SELECT SUM(lo_extendedprice) FROM {t} WHERE d_year = 1993 "
      "AND lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25")
Q2 = ("SELECT d_year, p_brand, SUM(lo_revenue) FROM {t} "
      "WHERE s_region = 'ASIA' GROUP BY d_year, p_brand LIMIT 10000")
Q3 = ("SET numGroupsLimit = 20000000; "
      "SELECT lo_orderkey, SUM(lo_revenue), COUNT(*) FROM {t} "
      "GROUP BY lo_orderkey ORDER BY lo_orderkey LIMIT 100000")
# numGroupsLimit = the reference default (100K): the device sort-trim keeps
# the smallest 100K keys per segment, which is exact for ORDER BY key ASC
# LIMIT 100K, and bounds the host-side state decode
Q6 = ("SET numGroupsLimit = 100000; "
      "SELECT lo_orderkey, DISTINCTCOUNT(lo_discount), SUM(lo_revenue) "
      "FROM {t} GROUP BY lo_orderkey ORDER BY lo_orderkey LIMIT 100000")
Q5 = ("SELECT pickup_day, DISTINCTCOUNT(passenger_count), "
      "PERCENTILETDIGEST(fare, 95) FROM taxi GROUP BY pickup_day LIMIT 1000")
# SSB Q4-style dimension join: filter + group on LOOKUP'd dim attributes —
# the TPU-first broadcast join (dim attrs ride the fact kernel as LUT
# gathers; reference pattern: LookupTransformFunction star joins)
Q7 = ("SELECT d_year, LOOKUP('brands', 'b_category', 'b_id', p_brand), "
      "SUM(lo_revenue) FROM {t} "
      "WHERE LOOKUP('brands', 'b_region', 'b_id', p_brand) = 'ASIA' "
      "GROUP BY d_year, LOOKUP('brands', 'b_category', 'b_id', p_brand) "
      "LIMIT 1000")
# MSE equi-join (the full V2 pipeline: device leaf selections → shuffle →
# sort-merge join, device-side when the key volume clears the gate —
# mse/device_join.py; reference pattern: HashJoinOperator two-table query).
# Filters keep the pair count bounded: ~4%·N ⋈ ~9%·N on a N/10-key space
# ≈ 0.036·N expected output pairs.
Q8 = ("SELECT a.d_year, COUNT(*), SUM(b.lo_revenue) FROM {t} a "
      "JOIN {t} b ON a.lo_orderkey = b.lo_orderkey "
      "WHERE a.lo_quantity < 3 AND b.lo_discount = 0 "
      "GROUP BY a.d_year ORDER BY a.d_year LIMIT 100")
# BASELINE config 3 verbatim shape: 3 SUM measures through one MXU pass
# (1 count + 3x3 limb planes with int8 limbs)
Q9 = ("SELECT d_year, p_brand, SUM(lo_revenue), SUM(lo_extendedprice), "
      "SUM(lo_quantity) FROM {t} WHERE s_region = 'ASIA' "
      "GROUP BY d_year, p_brand LIMIT 10000")
# LEFT outer variant of q8: the build-side ON conjunct must stay join
# residual (a WHERE would flip the semantics to INNER), exercising the
# fused kernel's masked-count path; unmatched probe rows keep COUNT(*)=1
# and NULL SUM. Selectivities match q8 → same ~0.036·N pair bound.
Q9J = ("SELECT a.d_year, COUNT(*), SUM(b.lo_revenue) FROM {t} a "
       "LEFT JOIN {t} b ON a.lo_orderkey = b.lo_orderkey "
       "AND b.lo_discount = 0 WHERE a.lo_quantity < 3 "
       "GROUP BY a.d_year ORDER BY a.d_year LIMIT 100")
# 2-join chain: the middle join is absorbed into the top fused stage
# (runtime chain absorption) so the whole pipeline crosses the host once.
# c's filter multiplies q8's pair bound by ~0.2 → ~0.007·N output pairs.
Q10 = ("SELECT a.d_year, COUNT(*), SUM(c.lo_revenue) FROM {t} a "
       "JOIN {t} b ON a.lo_orderkey = b.lo_orderkey "
       "JOIN {t} c ON b.lo_orderkey = c.lo_orderkey "
       "WHERE a.lo_quantity < 3 AND b.lo_discount = 0 "
       "AND c.lo_quantity < 2 "
       "GROUP BY a.d_year ORDER BY a.d_year LIMIT 100")
# live-ingest config: a CONSUMING (mutable) segment executed on the
# realtime device planes (realtime/device_plane.py). The timed loop runs
# against a plane-resident snapshot; the config additionally records the
# delta-upload economics (rt_full_bytes vs rt_delta_bytes vs
# rt_warm_bytes) that the bench gate pins.
Q11R = ("SELECT site, SUM(clicks), SUM(revenue), COUNT(*) FROM rt "
        "GROUP BY site ORDER BY site LIMIT 100")

RUNS = {
    "q1": ("q1_filter_sum", Q1.format(t="ssb"), "ssb", 1.0, 0.0),
    "q2": ("q2_groupby", Q2.format(t="ssb"), "ssb", 1.0, 0.0),
    "q3": ("q3_highcard_groupby", Q3.format(t="ssb"), "ssb", 1 / 3, 0.0),
    "q4": ("q4_combine16", Q2.format(t="ssb16"), "ssb16", 1.0, 0.0),
    # PERCENTILETDIGEST is approximate on BOTH paths. The device side is
    # bounded by the adaptive histogram's refined bucket width —
    # range/bins^2 around the asked quantile (~0.05% here, ops/kernels.py
    # "hist_adaptive"); the residual is the HOST oracle's own t-digest
    # tail error (value-fed digest, compression 100: observed ~1% at p95
    # on gamma fares — consistent with t-digest's q(1-q)/compression rank
    # bound mapped through the tail density). 2% covers the host digest.
    "q5": ("q5_distinct_tdigest", Q5, "taxi", 1 / 3, 0.02),
    "q6": ("q6_sparse_distinct", Q6.format(t="ssb"), "ssb", 1 / 3, 0.0),
    "q7": ("q7_lookup_join", Q7.format(t="ssb"), "ssb", 1.0, 0.0),
    "q8": ("q8_mse_join", Q8.format(t="ssb"), "ssb", 1 / 3, 0.0),
    "q9": ("q9_groupby_3sums", Q9.format(t="ssb"), "ssb", 1.0, 0.0),
    "q9j": ("q9j_mse_left_join", Q9J.format(t="ssb"), "ssb", 1 / 3, 0.0),
    "q10": ("q10_mse_join_chain", Q10.format(t="ssb"), "ssb", 1 / 3, 0.0),
    # multi-segment (16) variants: the stacked segment-batching configs —
    # num_device_dispatches should track batch FAMILIES, not segments
    "q3m": ("q3m_highcard_groupby16", Q3.format(t="ssb16"), "ssb16",
            1 / 3, 0.0),
    "q6m": ("q6m_sparse_distinct16", Q6.format(t="ssb16"), "ssb16",
            1 / 3, 0.0),
    # live-ingest table built in-process (tname "rt" needs no prebuilt
    # table dirs); run_single short-circuits into _run_realtime_single
    "q11r": ("q11r_realtime_ingest", Q11R, "rt", 1 / 3, 0.0),
}

N_BRANDS = 1000
BRAND_CATEGORIES = 40
BRAND_REGIONS = ["AMERICA", "ASIA", "EUROPE", "AFRICA", "MIDDLE EAST"]


def _register_brands_dim():
    """In-process dimension table for q7 (reference: isDimTable tables are
    fully replicated; here the registry is process-local)."""
    from pinot_tpu.engine.dim_tables import register_dimension_table

    register_dimension_table("brands", "b_id", {
        "b_id": np.arange(N_BRANDS, dtype=np.int32),
        "b_category": np.asarray(
            [f"MFGR#{i % BRAND_CATEGORIES}" for i in range(N_BRANDS)],
            dtype=object),
        "b_region": np.asarray(
            [BRAND_REGIONS[i % len(BRAND_REGIONS)] for i in range(N_BRANDS)],
            dtype=object),
    })


def _gen_ssb(rows: int, seed: int = 2024):
    rng = np.random.default_rng(seed)
    return {
        "d_year": rng.integers(1992, 1999, rows).astype(np.int32),
        "p_brand": (rng.integers(0, 1000, rows)).astype(np.int32),
        "s_region": np.asarray(["AMERICA", "ASIA", "EUROPE", "AFRICA", "MIDDLE EAST"],
                               dtype=object)[rng.integers(0, 5, rows)],
        "lo_discount": rng.integers(0, 11, rows).astype(np.int32),
        "lo_quantity": rng.integers(1, 51, rows).astype(np.int32),
        "lo_extendedprice": rng.integers(1, 55_001, rows).astype(np.int32),
        "lo_revenue": rng.integers(1, 600_000, rows).astype(np.int32),
        # high-card key for the sparse group-by config (~rows/10 distinct),
        # SORTED in ingestion order like real SSB lineorder (rows arrive in
        # orderkey order) — the segment builder records is_sorted and q3/q6
        # ride the sparse-presorted (zero-sort) kernel path. Only the
        # marginal distribution matters to the other configs, so sorting
        # this one column changes nothing else.
        "lo_orderkey": np.sort(
            rng.integers(0, max(1 << 22, rows // 10), rows)).astype(np.int32),
    }


def _ssb_schema(name: str):
    from pinot_tpu.spi.data_types import Schema

    return Schema.build(
        name,
        dimensions=[("d_year", "INT"), ("p_brand", "INT"), ("s_region", "STRING"),
                    ("lo_discount", "INT"), ("lo_quantity", "INT"),
                    ("lo_orderkey", "INT")],
        metrics=[("lo_extendedprice", "INT"), ("lo_revenue", "INT")],
    )


def _taxi_schema():
    from pinot_tpu.spi.data_types import Schema

    return Schema.build(
        "taxi",
        dimensions=[("pickup_day", "INT"), ("passenger_count", "INT")],
        metrics=[("fare", "DOUBLE")],
    )


def _build(schema, cols, out_dir, seg_name, no_dict=()):
    from pinot_tpu.segment.builder import SegmentBuilder
    from pinot_tpu.spi.table_config import IndexingConfig, TableConfig

    cfg = TableConfig(table_name=schema.schema_name, indexing=IndexingConfig(
        no_dictionary_columns=list(no_dict)))
    t0 = time.perf_counter()
    SegmentBuilder(schema, cfg, seg_name).build(cols, out_dir)
    print(f"[bench] built {seg_name} ({len(next(iter(cols.values()))):,} rows) "
          f"in {time.perf_counter()-t0:.1f}s", file=sys.stderr)


def prepare_tables(need_ssb, need_ssb16, need_taxi):
    """Build (once, cached on disk) and return {table: (schema, seg_dirs)}."""
    out = {}
    ssb_cols = None
    if need_ssb or need_ssb16:
        schema = _ssb_schema("ssb")
        d = CACHE / f"ssb_{ROWS}_v4"
        if not (d / "metadata.json").exists():
            ssb_cols = _gen_ssb(ROWS)
            print(f"[bench] generating ssb {ROWS:,} rows", file=sys.stderr)
            _build(schema, ssb_cols, d, "ssb_0",
                   no_dict=["lo_extendedprice", "lo_revenue",
                            "lo_quantity"])
        out["ssb"] = (schema, [d])
    if need_ssb16:
        schema16 = _ssb_schema("ssb16")
        dirs = [CACHE / f"ssb16_{ROWS}_v4" / f"s{i}" for i in range(16)]
        if not (dirs[-1] / "metadata.json").exists():
            if ssb_cols is None:
                ssb_cols = _gen_ssb(ROWS)
            bounds = np.linspace(0, ROWS, 17, dtype=np.int64)
            for i in range(16):
                sl = slice(int(bounds[i]), int(bounds[i + 1]))
                _build(schema16, {k: v[sl] for k, v in ssb_cols.items()},
                       dirs[i], f"ssb16_{i}",
                       no_dict=["lo_extendedprice", "lo_revenue",
                                "lo_quantity"])
        out["ssb16"] = (schema16, dirs)
    del ssb_cols
    if need_taxi:
        schema = _taxi_schema()
        d = CACHE / f"taxi_{ROWS}"
        if not (d / "metadata.json").exists():
            rng = np.random.default_rng(7)
            print(f"[bench] generating taxi {ROWS:,} rows", file=sys.stderr)
            cols = {
                "pickup_day": rng.integers(0, 730, ROWS).astype(np.int32),
                "passenger_count": rng.integers(1, 9, ROWS).astype(np.int32),
                "fare": np.round(rng.gamma(3.0, 9.0, ROWS), 2),
            }
            _build(schema, cols, d, "taxi_0", no_dict=["fare"])
        out["taxi"] = (schema, [d])
    return out


def _remaining() -> float:
    return TIME_BUDGET_S - (time.monotonic() - _START)


# --------------------------------------------------------------------------
# parent: orchestrate per-config children
# --------------------------------------------------------------------------

def _runner_shape(results=None) -> dict:
    """Self-describing runner-shape block for the round payload: physical
    and logical core counts plus the mesh device count any config actually
    ran with. Rounds recorded on differently-shaped machines are not
    timing-comparable (the r05→r06 q5/q7 'regressions' tracked a core-count
    change, not the code) — bench_gate downgrades same-platform timing
    FAILs to WARNs when these blocks differ."""
    logical = os.cpu_count() or 1
    physical = None
    try:
        pairs = set()
        for block in Path("/proc/cpuinfo").read_text().split("\n\n"):
            phys = core = None
            for line in block.splitlines():
                if line.startswith("physical id"):
                    phys = line.split(":", 1)[1].strip()
                elif line.startswith("core id"):
                    core = line.split(":", 1)[1].strip()
            if phys is not None and core is not None:
                pairs.add((phys, core))
        physical = len(pairs) or None
    except OSError:
        pass
    shape = {"logicalCores": logical, "physicalCores": physical or logical}
    mesh = None
    for v in (results or {}).values():
        if isinstance(v, dict) and v.get("mesh_devices"):
            mesh = v["mesh_devices"]
    if mesh:
        shape["meshDevices"] = mesh
    return shape


def _emit(results, platform, notes, skipped, final=False, statuses=None):
    """(Re-)print the one-line summary JSON; also persist it to PARTIAL.
    ALWAYS emits — a per-config failure must never leave the driver with
    rc!=0 and no JSON line: with zero completed configs the line carries
    value 0 and the per-config statuses instead of vanishing."""
    if "q2_groupby" in results:
        hname = "q2_groupby"
        # row count rides in the name so scaled runs never masquerade
        # as the 100M-row series
        metric = f"ssb_{ROWS // 1_000_000}m_q2_filter_groupby_rows_per_sec_per_chip"
    elif results:
        hname = next(iter(results))
        metric = f"{hname}_rows_per_sec_per_chip"
    else:
        hname = None
        metric = f"ssb_{ROWS // 1_000_000}m_q2_filter_groupby_rows_per_sec_per_chip"
    headline = results.get(hname) if hname else None
    speedup = headline.get("speedup") if headline else None
    out = {
        "metric": metric,
        "value": round(headline["rows_per_sec"]) if headline else 0,
        "unit": "rows/s",
        # null (not 0) when the baseline was skipped — 0 would read as a
        # measured 0x speedup
        "vs_baseline": round(speedup, 2) if speedup is not None else None,
        "detail": {k: {kk: (round(vv, 6) if isinstance(vv, float) else vv)
                       for kk, vv in v.items()} for k, v in results.items()},
        "rows": ROWS,
        "host_threads": os.cpu_count() or 1,
        # this machine exposes ONE core to Python (os.cpu_count()=1), so
        # the numpy host engine baseline is inherently single-threaded
        # here — compare rows/s + roofline fractions, not just speedup
        "host_baseline": f"numpy engine, {os.cpu_count() or 1} core(s)",
        "platform": platform,
        "runner": _runner_shape(results),
        "final": final,
    }
    if not results:
        out["error"] = "no benchmark config completed"
    if notes:
        out["warning"] = "; ".join(notes)
    if skipped:
        out["skipped_configs"] = skipped
    if statuses:
        # one status per requested config: ok / hung / skipped:<why> /
        # failed:rc=<n> — the per-config audit trail for partial runs
        out["configs"] = statuses
    line = json.dumps(out)
    print(line, flush=True)
    try:
        PARTIAL.mkdir(exist_ok=True)
        (PARTIAL / "summary.json").write_text(line)
    except Exception:
        pass


def orchestrate():
    import subprocess

    # the parent must NEVER initialize the accelerator backend (a chip
    # belongs to one process at a time; the parent holding it would starve
    # the children) — pin it to CPU before any pinot_tpu import can pull
    # jax in.
    os.environ["JAX_PLATFORMS"] = "cpu"

    # "" = the children's default backend, which must be a TPU
    platform_req = os.environ.get("BENCH_PLATFORM", "")
    notes = []

    need_ssb = any(RUNS[c][2] == "ssb" for c in CONFIGS if c in RUNS)
    need_ssb16 = any(RUNS[c][2] == "ssb16" for c in CONFIGS if c in RUNS)
    prepare_tables(need_ssb, need_ssb16, "q5" in CONFIGS)

    PARTIAL.mkdir(exist_ok=True)
    stage = PARTIAL.parent / (PARTIAL.name + "_stage")
    stage.mkdir(exist_ok=True)
    results, skipped = {}, []
    statuses: dict = {}
    platform_seen = None
    configs = [c for c in CONFIGS if c in RUNS]
    hung = False
    for i, cfg in enumerate(configs):
        name = RUNS[cfg][0]
        rem = _remaining()
        if hung or rem < 60:
            skipped.append(name)
            statuses[cfg] = ("skipped:previous config hung" if hung
                             else "skipped:time budget exhausted")
            print(f"[bench] SKIP {name}: "
                  + ("previous config hung" if hung else "time budget exhausted"),
                  file=sys.stderr)
            continue
        # fair share of the remaining budget, floor 120s (if we have it)
        share = max(min(120.0, rem - 30), rem / (len(configs) - i))
        outfile = stage / f"{cfg}.json"
        outfile.unlink(missing_ok=True)
        env = dict(os.environ)
        env["BENCH_DEADLINE_S"] = str(share)
        if platform_req:
            env["BENCH_PLATFORM"] = platform_req
            env["JAX_PLATFORMS"] = platform_req
        else:
            env.pop("BENCH_PLATFORM", None)
            env.pop("JAX_PLATFORMS", None)
        if platform_req == "cpu":
            # a CPU child can still exercise the mesh-sharded dispatch path
            # by splitting the host platform into N virtual devices — the
            # mesh round then measures real cross-chip-combine mechanics
            try:
                mesh_n = int(os.environ.get("BENCH_MESH_DEVICES", "8"))
            except ValueError:
                mesh_n = 8
            flag = f"--xla_force_host_platform_device_count={mesh_n}"
            xla = env.get("XLA_FLAGS", "")
            if mesh_n > 1 and "xla_force_host_platform_device_count" not in xla:
                env["XLA_FLAGS"] = (xla + " " + flag).strip()
        print(f"[bench] -> {cfg} (budget {share:.0f}s)", file=sys.stderr,
              flush=True)
        proc = subprocess.Popen(
            [sys.executable, __file__, "--config", cfg, "--out", str(outfile)],
            stdout=sys.stderr, stderr=sys.stderr, env=env,
            start_new_session=True)
        grace = share + 240  # child self-limits; grace covers init+build+host
        t0 = time.monotonic()
        while proc.poll() is None and time.monotonic() - t0 < grace \
                and _remaining() > 20:
            time.sleep(2.0)
        if proc.poll() is None:
            # the child holds the chip: kill it (own session → whole
            # group) so nothing outlives the run, and skip the rest
            print(f"[bench] {cfg} unresponsive after {grace:.0f}s; killing",
                  file=sys.stderr)
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
            proc.wait()
            notes.append(f"{cfg} hung and was killed")
            hung = True
            statuses[cfg] = "hung"
            skipped.append(name)
            continue
        if outfile.exists():
            try:
                payload = json.loads(outfile.read_text())
                (PARTIAL / f"{cfg}.json").write_text(outfile.read_text())
                platform_seen = payload.pop("platform", platform_seen)
                note = payload.pop("note", None)
                if note:
                    notes.append(note)
                results[name] = payload
                statuses[cfg] = "ok"
            except Exception as e:
                notes.append(f"{cfg} result unreadable: {e}")
                statuses[cfg] = f"failed:unreadable result ({e})"
                skipped.append(name)
        else:
            notes.append(f"{cfg} child exited rc={proc.returncode} "
                         f"with no result")
            statuses[cfg] = f"failed:rc={proc.returncode}"
            skipped.append(name)
        _emit(results, platform_seen or platform_req or "unknown", notes,
              skipped, statuses=statuses)

    # always emit the final line — even a fully-failed run must leave the
    # driver one parseable JSON record of WHAT failed and on which platform
    _emit(results, platform_seen or platform_req or "unknown", notes, skipped,
          final=True, statuses=statuses)
    return len(results)


# --------------------------------------------------------------------------
# child: run exactly one config, bounded by an internal deadline
# --------------------------------------------------------------------------

def _set_compile_cache(jax, platform: str) -> None:
    """Persist compiles across bench runs. Where JAX_COMPILATION_CACHE_DIR
    is set JAX already uses it and nothing here sets another; otherwise
    one fixed directory inside the checkout (the path is part of the cache
    key, so it must never move). CPU runs keep a directory of their own:
    CPU AOT entries are machine-feature-sensitive, TPU entries are not."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        name = ".jax_cache_chip" if platform == "tpu" \
            else f".jax_cache_bench_{platform}"
        jax.config.update("jax_compilation_cache_dir", str(ROOT / name))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def _init_backend():
    """(jax, platform). Without BENCH_PLATFORM the default backend must be
    a TPU: a measurement run that finds none fails, it never falls back."""
    import jax

    want = os.environ.get("BENCH_PLATFORM")
    if want:
        jax.config.update("jax_platforms", want)
    devs = jax.devices()
    print(f"[bench] devices: {devs}", file=sys.stderr)
    platform = devs[0].platform
    if not want and platform != "tpu":
        raise SystemExit(
            f"[bench] no TPU (jax found {platform!r}); pass "
            "BENCH_PLATFORM=cpu for a CPU dry run")
    _set_compile_cache(jax, platform)
    return jax, platform


def _plan_bytes(qe, sql, segments):
    """Column-plane bytes one execution must read (device roofline input)."""
    from pinot_tpu.query.parser.sql import parse_sql

    try:
        query = parse_sql(sql)
        total = 0
        for seg in segments:
            plan = qe.tpu.plan(query, seg)
            view = qe.tpu.cache.view(seg)
            arrays, _ = plan.gather_arrays_packed(view)
            total += sum(int(np.asarray(a).nbytes) if not hasattr(a, "nbytes")
                         else int(a.nbytes) for a in arrays)
        return total
    except Exception:
        return None


def _rows_match(a, b, rel_tol=0.0) -> bool:
    if len(a) != len(b):
        return False
    if rel_tol == 0.0:
        return sorted(map(repr, a)) == sorted(map(repr, b))

    def key(row):
        return tuple(x for x in row if not isinstance(x, float))

    bm = {key(r): r for r in b}
    for r in a:
        other = bm.get(key(r))
        if other is None:
            return False
        for x, y in zip(r, other):
            if isinstance(x, float) and isinstance(y, float):
                if abs(x - y) > rel_tol * max(1.0, abs(x), abs(y)):
                    return False
    return True


def _plan_first_segment(qe, sql, segs):
    """(executor, seg0, compiled plan) for the single-stage device path,
    or None when the shape doesn't ride it (e.g. the MSE join config)."""
    from pinot_tpu.query.parser.sql import parse_sql

    try:
        query = parse_sql(sql)
        ex = qe.tpu
        seg = segs[0]
        return ex, seg, ex.plan(query, seg)
    except Exception:
        return None


def _kernel_time_est(planned, deadline, iters: int = 5):
    """Pure device-kernel seconds for one segment's program: median of
    (dispatch TWO kernels + one fetch) minus (ONE kernel + one fetch).
    The device executes in order, so the last output materializes after
    both kernels; the delta is the second kernel's compute with every
    fixed dispatch/fetch cost cancelled. Residual bias: the second
    dispatch's HOST-side work (~1ms of plan/pack per dispatch) overlaps
    kernel #1 only partially, so for sub-millisecond kernels kernel_s is
    an UPPER bound on device compute, not an exact reading. Deadline-aware
    (measurement is OPTIONAL — it must never eat the host baseline's
    budget); returns None without at least 2+2 clean rounds or a positive
    delta."""
    if planned is None:
        return None
    ex, seg, plan = planned

    def run(k):
        t0 = time.perf_counter()
        outs = None
        for _ in range(k):
            outs = ex.dispatch_plan(seg, plan)
        if hasattr(outs, "flat"):
            np.asarray(outs.flat)
        else:
            for o in outs:
                np.asarray(o)
        return time.perf_counter() - t0

    singles, doubles = [], []
    try:
        run(1)  # warm
        for _ in range(iters):
            if time.monotonic() > deadline:
                break
            singles.append(run(1))
        for _ in range(iters):
            if time.monotonic() > deadline:
                break
            doubles.append(run(2))
    except Exception:
        return None
    if len(singles) < 2 or len(doubles) < 2:
        return None
    delta = float(np.median(doubles) - np.median(singles))
    # a non-positive delta is measurement noise — suppress rather than
    # emit absurd derived rates
    return delta if delta > 0 else None


def _run_realtime_single(outpath: str):
    """q11r: a CONSUMING (mutable) segment executed on the realtime device
    planes. Beyond the usual cold/warm p50s the payload records the
    delta-upload economics the bench gate pins:

      rt_full_bytes  — bytes uploaded by the FIRST query (cold: the whole
                       snapshot crosses to the device),
      rt_delta_bytes — bytes uploaded by the first query AFTER appending
                       ~1% more rows (only the new tail may cross;
                       rt_delta_bytes >= rt_full_bytes means the
                       incremental path is gone),
      rt_warm_bytes  — bytes uploaded by a repeat on an unchanged
                       generation (must stay 0: plane-resident fast path).

    The row count is deliberately modest (BENCH_RT_ROWS, default 200k):
    MutableSegment.index() is per-row host-side work, and the quantity
    under test is upload BYTES, which scale linearly anyway.
    """
    name = RUNS["q11r"][0]
    deadline = time.monotonic() + float(os.environ.get("BENCH_DEADLINE_S", 600))
    jax, platform = _init_backend()
    note = None
    from pinot_tpu.engine.query_executor import QueryExecutor
    from pinot_tpu.ingestion.transform import build_transform_pipeline
    from pinot_tpu.realtime.device_plane import (realtime_stats,
                                                 reset_realtime_stats)
    from pinot_tpu.segment.mutable import MutableSegment
    from pinot_tpu.spi.data_types import Schema

    n = int(os.environ.get("BENCH_RT_ROWS", 200_000))
    delta_n = max(256, n // 100)
    total = n + delta_n
    schema = Schema.build(
        "rt",
        dimensions=[("site", "STRING"), ("code", "INT")],
        metrics=[("clicks", "INT"), ("revenue", "LONG")])
    rng = np.random.default_rng(7)
    sites = [f"site{i:02d}" for i in range(64)]
    site_idx = rng.integers(0, 64, total)
    code = rng.integers(0, 1000, total)
    clicks = rng.integers(0, 100, total)
    revenue = rng.integers(0, 10_000, total)
    seg = MutableSegment(schema, "rt_live_0")
    pipe = build_transform_pipeline(schema)

    def feed(lo: int, hi: int):
        for i in range(lo, hi):
            seg.index(pipe.transform({
                "site": sites[site_idx[i]], "code": int(code[i]),
                "clicks": int(clicks[i]), "revenue": int(revenue[i])}))

    feed(0, n)
    tpu = QueryExecutor(backend="tpu")
    host = QueryExecutor(backend="host")
    for qe in (tpu, host):
        qe.add_table(schema, [seg], name="rt")
    sql = RUNS["q11r"][1]
    # caches off so every timed iteration exercises the device execution
    # path; the planes themselves are NOT a cache tier — they persist
    # across iterations, so only the first run uploads
    nocache = "SET segmentCache = false; SET resultCache = false; " + sql

    reset_realtime_stats()
    r = tpu.execute_sql(nocache)  # cold: full snapshot upload + compile
    if r.exceptions:
        raise RuntimeError(f"{nocache}: {r.exceptions}")
    rt_full_bytes = int(realtime_stats()["deltaBytes"])

    # steady-state loop: generation unchanged → plane-resident, 0 uploads
    target_iters = max(3, round(ITERS / 3))
    times = []
    while len(times) < target_iters and (
            not times or time.monotonic() + min(times) < deadline):
        t0 = time.perf_counter()
        r = tpu.execute_sql(nocache)
        times.append(time.perf_counter() - t0)
    if r.exceptions:
        raise RuntimeError(f"{nocache}: {r.exceptions}")
    p50 = float(np.median(times))

    # warm repeat with caching at defaults on the SAME generation: the
    # partial tiers serve it and the planes must upload nothing
    warm_p50 = warm_match = None
    rt_warm_bytes = None
    try:
        rw = tpu.execute_sql(sql)  # populate
        reset_realtime_stats()
        warm_times = []
        while len(warm_times) < min(target_iters, 5) and (
                not warm_times
                or time.monotonic() + min(warm_times) < deadline):
            t0 = time.perf_counter()
            rw = tpu.execute_sql(sql)
            warm_times.append(time.perf_counter() - t0)
        if not rw.exceptions:
            warm_p50 = float(np.median(warm_times))
            warm_match = _rows_match(r.result_table.rows,
                                     rw.result_table.rows, 0.0)
            rt_warm_bytes = int(realtime_stats()["deltaBytes"])
    except Exception:
        pass  # warm numbers are additive; never fail the config

    # ingest ~1% more rows, query again with caches off: only the new
    # tail should cross (delta upload, generation bump)
    feed(n, total)
    reset_realtime_stats()
    t0 = time.perf_counter()
    rd = tpu.execute_sql(nocache)
    delta_query_s = time.perf_counter() - t0
    if rd.exceptions:
        raise RuntimeError(f"post-delta {nocache}: {rd.exceptions}")
    rt_delta_bytes = int(realtime_stats()["deltaBytes"])

    # host baseline at the SAME generation: live-ingest bit-identity
    rh = host.execute_sql(sql)
    if rh.exceptions:
        raise RuntimeError(f"host {sql}: {rh.exceptions}")
    match = _rows_match(rd.result_table.rows, rh.result_table.rows, 0.0)

    payload = {
        "tpu_p50_s": p50,
        "rows_per_sec": n / p50,
        "cold_p50_s": p50,
        "warm_p50_s": warm_p50,
        "warm_speedup": (p50 / warm_p50) if warm_p50 else None,
        "warm_match": warm_match,
        "match": match,
        "iters": len(times),
        "platform": platform,
        "num_device_dispatches": getattr(rd, "num_device_dispatches", 0),
        "num_compiles": getattr(rd, "num_compiles", 0),
        "rt_rows": n,
        "rt_delta_rows": delta_n,
        "rt_full_bytes": rt_full_bytes,
        "rt_delta_bytes": rt_delta_bytes,
        "rt_warm_bytes": rt_warm_bytes,
        "rt_delta_query_s": delta_query_s,
    }
    if note:
        payload["note"] = note
    print(f"[bench] {name}: p50 {p50*1000:.1f}ms, full upload "
          f"{rt_full_bytes}B, +{delta_n} rows → delta {rt_delta_bytes}B, "
          f"warm {rt_warm_bytes}B, match={match}, warm_match={warm_match}",
          file=sys.stderr)
    tmp = Path(outpath + ".tmp")
    tmp.write_text(json.dumps(payload))
    tmp.replace(outpath)


def run_single(cfg: str, outpath: str):
    if cfg == "q11r":
        return _run_realtime_single(outpath)
    name, sql, tname, iter_frac, tol = RUNS[cfg]
    deadline = time.monotonic() + float(os.environ.get("BENCH_DEADLINE_S", 600))
    jax, platform = _init_backend()
    note = None
    from pinot_tpu.engine.query_executor import QueryExecutor
    from pinot_tpu.segment.loader import load_segment

    tables = prepare_tables(tname in ("ssb",), tname == "ssb16",
                            tname == "taxi")
    schema, dirs = tables[tname]
    segs = [load_segment(d) for d in dirs]
    ncpu = os.cpu_count() or 1
    tpu = QueryExecutor(backend="tpu")
    host = QueryExecutor(backend="host", num_threads=ncpu)
    for qe in (tpu, host):
        qe.add_table(schema, segs)
    if cfg == "q7":
        _register_brands_dim()

    target_iters = max(3, round(ITERS * iter_frac)) if iter_frac < 1 else ITERS

    r = tpu.execute_sql(sql)  # warmup / compile / HBM residency
    if r.exceptions:
        raise RuntimeError(f"{sql}: {r.exceptions}")
    # COLD loop: segment-cache off, so tpu_p50_s keeps measuring the
    # device execution path across rounds (cache/partial.py would
    # otherwise zero it from the second iteration on). Shapes whose engine
    # rejects the SET (e.g. the MSE join) time the plain SQL instead.
    # resultCache also off: the MSE stage-plan cache would serve every
    # iteration after the first and zero out the cold p50
    cold_sql = "SET segmentCache = false; SET resultCache = false; " + sql
    # MESH mode: with >1 local device the engine shards batch families by
    # default, so the solo baseline must force meshExecution=false to keep
    # tpu_p50_s comparable across rounds; the mesh-on variant is timed in
    # its own loop below and emitted as mesh_p50_s / mesh_speedup.
    try:
        mesh_ndev = len(jax.devices())
    except Exception:
        mesh_ndev = 1
    mesh_sql = None
    if mesh_ndev > 1:
        mesh_sql = cold_sql
        cold_sql = "SET meshExecution = false; " + cold_sql
    probe = tpu.execute_sql(cold_sql)
    if probe.exceptions:
        cold_sql = sql
        mesh_sql = None
    times = []
    while len(times) < target_iters and (
            not times or time.monotonic() + min(times) < deadline):
        t0 = time.perf_counter()
        r = tpu.execute_sql(cold_sql)
        times.append(time.perf_counter() - t0)
    if r.exceptions:
        raise RuntimeError(f"{cold_sql}: {r.exceptions}")
    p50 = float(np.median(times))

    # mesh-on loop: same cold semantics (segmentCache=false), sharded
    # dispatch across all local devices; match is bit-identity (tol 0.0)
    mesh_p50 = mesh_match = None
    if mesh_sql is not None:
        try:
            rm = tpu.execute_sql(mesh_sql)
            if not rm.exceptions:
                mesh_times = []
                while len(mesh_times) < min(target_iters, 5) and (
                        not mesh_times
                        or time.monotonic() + min(mesh_times) < deadline):
                    t0 = time.perf_counter()
                    rm = tpu.execute_sql(mesh_sql)
                    mesh_times.append(time.perf_counter() - t0)
                if not rm.exceptions and mesh_times:
                    mesh_p50 = float(np.median(mesh_times))
                    mesh_match = _rows_match(r.result_table.rows,
                                             rm.result_table.rows, 0.0)
        except Exception:
            mesh_p50 = None  # mesh numbers are additive; never fail

    # WARM repeat loop: default caching on — the first run populates the
    # partial tiers, the timed repeats should hit with zero dispatches.
    warm_p50 = warm_match = None
    rw = None
    try:
        rw = tpu.execute_sql(sql)  # populate
        warm_times = []
        while len(warm_times) < min(target_iters, 5) and (
                not warm_times
                or time.monotonic() + min(warm_times) < deadline):
            t0 = time.perf_counter()
            rw = tpu.execute_sql(sql)
            warm_times.append(time.perf_counter() - t0)
        if rw.exceptions:
            rw = None
        else:
            warm_p50 = float(np.median(warm_times))
            warm_match = _rows_match(r.result_table.rows,
                                     rw.result_table.rows, tol)
    except Exception:
        rw = None  # warm numbers are additive; never fail the config

    # one traced run OUTSIDE the timed loop (it takes the untraced path,
    # but allocates spans): per-phase attribution for the BENCH json
    phases = None
    try:
        rt = tpu.execute_sql("SET trace = true; " + sql)
        if not rt.exceptions and rt.trace_info:
            from pinot_tpu.spi.trace import phase_breakdown

            phases = phase_breakdown(rt.trace_info)
    except Exception:
        pass  # tracing is diagnostics; never fail the bench numbers

    # host baseline: the FIRST run is bounded by the remaining deadline —
    # an unbounded host run would blow the child's share and make the
    # parent skip every later config. On timeout the TPU numbers still land,
    # with match=None + a note instead of a hung child.
    host_holder: dict = {}

    def _host_once():
        t0 = time.perf_counter()
        try:
            resp = host.execute_sql(sql)
        except BaseException as e:  # noqa: BLE001 — surfaced to the child
            host_holder["result"] = ("exc", e, None)
            return
        host_holder["result"] = ("ok", resp, time.perf_counter() - t0)

    import threading

    th = threading.Thread(target=_host_once, daemon=True)
    th.start()
    th.join(timeout=max(5.0, deadline - time.monotonic()))
    status, rh, host_first_s = host_holder.get("result") or ("timeout",) * 3
    if status == "exc":
        raise rh  # a real host-engine failure must fail the config loudly
    host_p50 = match = None
    if status == "ok":
        if rh.exceptions:
            raise RuntimeError(f"host {sql}: {rh.exceptions}")
        host_times = [host_first_s]
        while len(host_times) < 2 and \
                time.monotonic() + host_times[0] < deadline:
            t0 = time.perf_counter()
            rh = host.execute_sql(sql)
            host_times.append(time.perf_counter() - t0)
        host_p50 = float(np.median(host_times))
        match = _rows_match(r.result_table.rows, rh.result_table.rows, tol)
    else:
        note = "; ".join(filter(None, [
            note, f"{name}: host baseline exceeded deadline, skipped"]))

    # kernel-only measurement LAST: optional, never at the expense of the
    # host-verified numbers above
    kernel_s = None
    if platform != "cpu":
        kernel_s = _kernel_time_est(
            _plan_first_segment(tpu, sql, segs), deadline)

    nbytes = _plan_bytes(tpu, sql, segs)
    payload = {
        "tpu_p50_s": p50,
        "rows_per_sec": ROWS / p50,
        "host_parallel_s": host_p50,
        "speedup": host_p50 / p50 if host_p50 is not None else None,
        "match": match,
        "iters": len(times),
        "platform": platform,
        # device-dispatch economics of the LAST timed run: dispatches
        # should track batch families (not segments) and steady-state
        # compiles should be 0
        "num_device_dispatches": getattr(r, "num_device_dispatches", 0),
        "num_compiles": getattr(r, "num_compiles", 0),
        # warm repeat-run series (cache/ tiers at their defaults): the cold
        # number above is measured with SET segmentCache=false so the two
        # are directly comparable on one engine instance
        "cold_p50_s": p50,
        "warm_p50_s": warm_p50,
        "warm_speedup": (p50 / warm_p50) if warm_p50 else None,
        "warm_match": warm_match,
    }
    if rw is not None:
        payload["warm_cache_hits"] = getattr(rw, "num_segments_cache_hit", 0)
        payload["warm_cache_misses"] = getattr(
            rw, "num_segments_cache_miss", 0)
        payload["warm_num_device_dispatches"] = getattr(
            rw, "num_device_dispatches", 0)
    if mesh_p50 is not None:
        # sharded-dispatch round: solo-vs-mesh on the same engine instance,
        # bit-identity required (mesh_match uses tol 0.0)
        payload["mesh_devices"] = mesh_ndev
        payload["mesh_p50_s"] = mesh_p50
        payload["mesh_match"] = mesh_match
        payload["mesh_speedup"] = p50 / mesh_p50 if mesh_p50 else None
    if note:
        payload["note"] = note
    if phases is not None:
        # compileMs/transferBytes sum the family_dispatch span
        # attributes; deviceWaitMs sums the DEVICE_FETCH spans (the host's
        # wait for the device); hostCombineMs sums the SERVER_COMBINE +
        # BROKER_REDUCE spans (see pinot_tpu/spi/trace.py:phase_breakdown)
        payload["phases"] = phases
    stage_stats = getattr(r, "mse_stage_stats", None)
    if stage_stats:
        # per-stage attribution (rows in/out, shuffled bytes, wall) from
        # the LAST timed tpu run — lets bench rounds split MSE time into
        # shuffle vs join vs agg
        payload["mse_stage_stats"] = {str(k): v
                                      for k, v in stage_stats.items()}
        # bytes that actually crossed a stage boundary (device handoffs
        # count 0); the bench gate fails MSE configs that regress this
        payload["shuffled_bytes"] = sum(
            st.get("cross_stage_bytes", st.get("shuffled_bytes", 0))
            for st in stage_stats.values())
        # device→host round-trips taken by fused stages (1 per fused plan;
        # a regression here means a plan fell back to per-operator hops)
        payload["host_crossings"] = sum(
            int(st.get("host_crossings", 0) or 0)
            for st in stage_stats.values())
    if kernel_s is not None:
        # measured pure-kernel time for ONE segment's program (all fixed
        # dispatch/fetch costs cancelled); per-segment bytes give the
        # kernel's true roofline fraction
        payload["kernel_s"] = kernel_s
        payload["kernel_rows_per_sec"] = \
            (ROWS / len(segs)) / max(kernel_s, 1e-9)
    if nbytes:
        payload["hbm_bytes"] = nbytes
        payload["hbm_bytes_per_sec"] = nbytes / p50
        payload["hbm_peak_frac"] = (nbytes / p50) / V5E_HBM_PEAK
        if kernel_s is not None:
            payload["kernel_hbm_peak_frac"] = \
                ((nbytes / len(segs)) / max(kernel_s, 1e-9)) / V5E_HBM_PEAK
    host_part = (f"host({ncpu}thr) {host_p50*1000:.0f}ms, "
                 f"speedup {host_p50/p50:.1f}x"
                 if host_p50 is not None else "host skipped (deadline)")
    warm_part = (f"warm {warm_p50*1000:.1f}ms ({p50/warm_p50:.1f}x, "
                 f"match={warm_match})" if warm_p50 else "warm skipped")
    mesh_part = (f"mesh[{mesh_ndev}] {mesh_p50*1000:.1f}ms "
                 f"({p50/mesh_p50:.2f}x, match={mesh_match}), "
                 if mesh_p50 else "")
    print(f"[bench] {name}: p50 {p50*1000:.1f}ms "
          f"({ROWS/p50/1e9:.2f}B rows/s), {mesh_part}{warm_part}, "
          f"{host_part}, match={match}"
          + (f", {nbytes/p50/1e9:.0f} GB/s "
             f"({100*(nbytes/p50)/V5E_HBM_PEAK:.0f}% v5e peak)"
             if nbytes else ""),
          file=sys.stderr)
    tmp = Path(outpath + ".tmp")
    tmp.write_text(json.dumps(payload))
    tmp.replace(outpath)


def main():
    if "--config" in sys.argv:
        cfg = sys.argv[sys.argv.index("--config") + 1]
        outpath = sys.argv[sys.argv.index("--out") + 1]
        run_single(cfg, outpath)
        return
    completed = orchestrate()
    # exit 0 when at least one config completed; a zero-config run still
    # emitted its JSON (with per-config statuses) before this nonzero exit
    sys.exit(0 if completed else 1)


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # still emit ONE parseable JSON line for the driver
        import traceback

        traceback.print_exc()
        if "--config" not in sys.argv:
            print(json.dumps({
                "metric": f"ssb_{ROWS // 1_000_000}m_q2_filter_groupby_rows_per_sec_per_chip",
                "value": 0,
                "unit": "rows/s",
                "vs_baseline": 0,
                "error": f"{type(e).__name__}: {e}",
            }))
        sys.exit(0 if "--config" not in sys.argv else 1)
