"""One run of one benchmark cell on the chip.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    JAX_PLATFORMS=cpu python benchmark/run.py --workload <cell> --rehearse ...
        the same control flow at toy size on the CPU; prints no metric

One process, as a deployment's smallest cluster: PropertyStore +
ClusterController + ServerInstance(backend="tpu") + Broker. Client threads
call ``broker.execute_sql`` and a request ends when its rows are in hand.
The rows of every timed request are kept and, once the window has closed,
compared with the plain NumPy reference over the generated columns.

What a cell is comes from data: ``BENCHMARK.json`` names the cell's
configuration, traffic mix and metrics; ``configs/``, ``traffic/``,
``queries/``, ``metrics/`` hold one file each, ``generators/``,
``references/`` and ``readers/`` one small module each. The last line of
stdout is the result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import faulthandler
import functools
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import types
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(ROOT), str(HERE), str(HERE / "readers")):
    if p not in sys.path:
        sys.path.insert(0, p)

import table  # noqa: E402
import traffic  # noqa: E402

EXIT_NO_DEVICE = 3
LAST_WAIT_S = 60.0  # a request in flight at the close is waited for
# every thread's stack is dumped after this long past the build (a compile
# that takes minutes on the chip shows up here, not in any CPU test)
WATCHDOG_S = 300.0


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class BenchFailure(Exception):
    pass


@functools.lru_cache(maxsize=None)
def module_at(path: Path):
    """A reader or a reference: a module found by its file name, loaded
    once (a reader that prints a table prints it once a run)."""
    if not path.is_file():
        raise FileNotFoundError(f"no module {path}")
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Record:
    """One timed request, as the client saw it."""

    __slots__ = ("cls", "params", "sql", "start", "end", "rows", "error",
                 "dispatches", "partial", "trace", "n_rows", "n_cols")

    def __init__(self, cls, params, sql, start, end, resp):
        table = resp.result_table
        self.cls, self.params, self.sql = cls, params, sql
        self.start, self.end = start, end
        rows = table.rows if table is not None else None
        self.rows = None if rows is None else [tuple(r) for r in rows]
        self.error = "; ".join(map(str, resp.exceptions)) or None
        if self.error is None and rows is None:
            self.error = "no result table"
        self.dispatches = resp.num_device_dispatches
        self.partial = bool(resp.partial_result)
        self.trace = resp.trace_info or None
        self.n_rows = len(rows) if rows else 0
        self.n_cols = len(rows[0]) if rows else 0


def host_memory_free() -> str:
    """The host's free memory, for the log: the chip's machine ends a
    command whose host memory runs out."""
    try:
        for line in open("/proc/meminfo"):
            if line.startswith("MemFree:"):
                return f"{int(line.split()[1]) / 2 ** 20:.1f} GiB"
    except OSError:
        pass
    return "unknown"


def watch_compiles() -> dict:
    """Count every XLA executable this process asks for (engine programs,
    output packs, stacking helpers alike), whether compiled or read from
    the persistent cache. Copied from chip_smoke.py."""
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


def fallback_events() -> dict:
    """fused-host, mesh-solo, device-join-host, sparse-combine-host: the
    program's own count of work that left the device path, over the whole
    process: one in the warm-up says as much about the window's path as
    one inside it."""
    from pinot_tpu.engine.perf_ledger import PERF_LEDGER
    from pinot_tpu.ops import fused_groupby

    totals = dict(PERF_LEDGER.snapshot()["fallbackEvents"]["total"])
    if fused_groupby._STATE["error"] is not None:
        totals["fused-disabled"] = totals.get("fused-disabled", 0) + 1
    return totals


def client_loop(broker, sequence, t_end, records, trace_prefix):
    """A closed loop: the next request goes out when the reply is in."""
    for cls, params, sql in sequence:
        start = time.perf_counter()
        if start >= t_end:
            return
        resp = broker.execute_sql(trace_prefix + sql)
        # the response holds its rows as a plain list: they are in hand and
        # the device work has ended
        end = time.perf_counter()
        records.append(Record(cls, params, sql, start, end, resp))


def watch_gc() -> list:
    """[(generation, seconds)] of every collection of the interpreter's
    cyclic collector from now on; traced runs only (a callback per
    collection is tracing, however cheap)."""
    import gc

    pauses, began = [], [0.0]

    def on_gc(phase, info):
        if phase == "start":
            began[0] = time.perf_counter()
        else:
            pauses.append((info["generation"],
                           time.perf_counter() - began[0]))

    gc.callbacks.append(on_gc)
    return pauses


def drive(broker, sequences, seconds, trace_prefix, slice_spec, trace_dir):
    """The measured window: one thread a client sequence for ``seconds``
    seconds; with ``slice_spec`` the profiler wraps a slice in its middle.
    Returns the records, the window's start, the clients still hung a
    minute after its close, and the slice's edges."""
    import jax

    per_client = [[] for _ in sequences]
    t0 = time.perf_counter()
    t_end = t0 + seconds
    threads = [threading.Thread(
        target=client_loop, name=f"client-{i}", daemon=True,
        args=(broker, sequence, t_end, per_client[i], trace_prefix))
        for i, sequence in enumerate(sequences)]
    for t in threads:
        t.start()
    sliced = None
    if slice_spec is not None:
        start_s = min(slice_spec["start_s"], seconds / 4)
        length = min(slice_spec["seconds"], seconds / 2)
        time.sleep(max(0.0, t0 + start_s - time.perf_counter()))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        lo = time.perf_counter()
        time.sleep(length)
        hi = time.perf_counter()
        jax.profiler.stop_trace()
        sliced = (lo, hi)
    deadline = t_end + LAST_WAIT_S
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()))
    hung = [t.name for t in threads if t.is_alive()]
    records = [r for rs in per_client for r in rs]
    return records, t0, hung, sliced


def warm_up(broker, workload, compiles) -> dict:
    """Some requests of every class (first touch: upload, compile or cache
    read), again, then the cell's own concurrency for a few seconds. All of
    it is set-up, with literals of its own and the mix's ``warm_set``
    options, so that it leaves nothing behind in the program's caches."""
    mix = workload.mix
    phases = {}
    picks = workload.warm_picks(mix["warm_set"])
    for phase in ("first_touch_s", "warm_pass_s"):
        t0 = time.perf_counter()
        for cls, params, sql in picks:
            resp = broker.execute_sql(sql)
            if resp.exceptions or resp.result_table is None:
                raise BenchFailure(f"warm-up of {cls} {params} failed: "
                                   f"{resp.exceptions}")
        phases[phase] = time.perf_counter() - t0
    t0 = time.perf_counter()
    if mix.get("warm_concurrent_s", 0) > 0 and mix["clients"] > 1:
        sequences = [workload.client_sequence(i, traffic.WARM,
                                              mix["warm_set"])
                     for i in range(mix["clients"])]
        records, _, hung, _ = drive(broker, sequences,
                                    mix["warm_concurrent_s"], "", None, None)
        bad = [r.error for r in records if r.error]
        if hung or bad:
            raise BenchFailure(f"concurrent warm-up failed: hung {hung}, "
                               f"errors {bad[:3]}")
    phases["warm_concurrent_s"] = time.perf_counter() - t0
    phases["xla_executables_setup"] = compiles["requests"]
    phases["xla_cache_hits_setup"] = compiles["hits"]
    phases["xla_seconds_setup"] = compiles["seconds"]
    return phases


def references(workload, config, rows_per_segment, seed, acc="exact",
               block_of=None) -> dict:
    """{class: its reference, with the whole table taken in}. The table is
    generated again from the seed, a segment at a time, by a few threads
    (NumPy releases the interpreter lock in its kernels). ``block_of``
    alters a segment's rows, or leaves the segment out by returning None."""
    from concurrent.futures import ThreadPoolExecutor

    generator = table.generator_of(config)
    names = generator.dictionaries(config)
    refs = {}
    for cls, qclass in workload.classes.items():
        mod = module_at(HERE / "references" / f"{qclass['reference']}.py")
        refs[cls] = mod.Reference(qclass, config, names, acc)

    def take_in(seg: int) -> None:
        block = generator.segment_columns(config, rows_per_segment, seed,
                                          seg)
        if block_of is not None:
            block = block_of(seg, block)
        if block is not None:
            for ref in refs.values():
                ref.add(block)

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(take_in, range(config["segments"])))
    return refs


def _key(cls, params) -> tuple:
    return cls, tuple(sorted(params.items()))


def reference_rows(config_name, mix_name, seed, rows_per_segment, picks,
                   acc, block_of) -> list:
    """Runs in a process of its own: the reference's answer to each of
    ``picks`` [(class, literals)]."""
    config = traffic.load("configs", config_name)
    workload = traffic.Workload(traffic.load("traffic", mix_name),
                                config["table"], seed)
    refs = references(workload, config, rows_per_segment, seed, acc,
                      block_of)
    return [refs[cls].answer(params) for cls, params in picks]


def reference_answers(cell, seed, rows_per_segment, records, acc="exact",
                      block_of=None) -> dict:
    """{(class, literals): the reference's rows} for every request in
    ``records``, computed in a spawned process that never touches JAX and
    gives all its memory back when it ends. The chip's host is slow to take
    back what a process unmaps: while a reference that passed whole
    segments through NumPy ran, the host's free memory fell by a gigabyte a
    second, and the machine ends a command at 40 GiB. ``acc`` and
    ``block_of`` are for tests/control.py alone."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    picks = {_key(r.cls, r.params): (r.cls, r.params)
             for r in records if not r.error}
    with ProcessPoolExecutor(
            1, mp_context=multiprocessing.get_context("spawn")) as pool:
        rows = pool.submit(
            reference_rows, cell["config"], cell["traffic"], seed,
            rows_per_segment, list(picks.values()), acc, block_of).result()
    return dict(zip(picks, rows))


def compare(records, workload, want) -> dict:
    """Every timed request's rows against the reference's answer to its
    literals. Python compares ints and floats by value, so a non-integral
    or off-by-one cell in a row fails it; nothing is rounded into
    agreement."""
    wrong, first = 0, None
    for r in records:
        if r.error:
            continue
        ref = want[_key(r.cls, r.params)]
        got = r.rows if workload.classes[r.cls]["ordered"] \
            else sorted(r.rows)
        if r.partial or got != ref:
            wrong += 1
            if first is None:
                first = (f"{r.cls} {r.params}: {len(got)} rows against "
                         f"{len(ref)}; first got {got[:2]} want {ref[:2]}; "
                         f"partial={r.partial}")
    return {"wrong": wrong, "first": first}


def percentile(values, q):
    import numpy as np

    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def end_to_end(records, t0, setup_s) -> dict:
    """Every end-to-end metric the harness can report; a cell reports those
    that BENCHMARK.json lists for it. (`ssb16.flight12` does not list the
    median: its latencies fall into clusters a flight-1 dispatch apart and
    the median sits on the edge between two of them.)"""
    lat = [(r.end - r.start) * 1000.0 for r in records]
    window = max(r.end for r in records) - t0
    return {
        "throughput_qps": len(records) / window,
        "latency_p50_ms": statistics.median(lat),
        "latency_p95_ms": percentile(lat, 95),
        "setup_s": setup_s,
    }


def cell_metrics(bench, cell, kind) -> list:
    """The cell's metrics of ``kind`` as BENCHMARK.json lists them."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def run_cell(args, after=None) -> int:
    """One run. ``after`` is for tests/control.py alone: called with what
    the run measured and compared, before the result line is printed, and
    what it returns is added to the line; a benchmark run has none."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"unknown workload {args.workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[args.workload]
    config = traffic.load("configs", cell["config"])
    mix = traffic.load("traffic", cell["traffic"])
    rows_per_segment = (config["rehearse"]["rows_per_segment"]
                        if args.rehearse else config["rows_per_segment"])
    total_rows = rows_per_segment * config["segments"]
    workload = traffic.Workload(mix, config["table"], args.seed)

    # the segment build is host NumPy that never imports JAX: its worker
    # processes start first and build while this process brings JAX up
    data_dir = Path(tempfile.mkdtemp(prefix="bench_segments_"))
    trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
    pool = table.BuildPool(config, rows_per_segment, args.seed, data_dir)
    server = None
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
        try:
            devs = jax.devices()
        except RuntimeError as e:
            say(f"JAX found no device: {e}")
            return EXIT_NO_DEVICE
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        want_platform = "cpu" if args.rehearse else "tpu"
        if device["platform"] != want_platform \
                or (not args.rehearse and device["count"] != cell["chips"]):
            say(f"cell {cell['name']} needs {cell['chips']} x "
                f"{want_platform}, JAX found {device}: no result")
            return EXIT_NO_DEVICE
        peaks = json.loads((HERE / "peaks.json").read_text())
        if not args.rehearse and device["kind"] not in peaks:
            raise BenchFailure(f"device kind {device['kind']!r} is not in "
                               "peaks.json")
        say(f"device {device}, jax {jax.__version__}, seed {args.seed}, "
            f"cell {cell['name']}, compile cache at "
            f"{jax.config.jax_compilation_cache_dir}")

        from pinot_tpu.cluster.broker import Broker
        from pinot_tpu.cluster.controller import ClusterController
        from pinot_tpu.cluster.server import ServerInstance
        from pinot_tpu.cluster.store import PropertyStore
        from pinot_tpu.spi.metrics import BROKER_METRICS, BrokerMeter

        store = PropertyStore()
        controller = ClusterController(store)
        server = ServerInstance(store, "Server_0", backend="tpu")
        server.start()
        broker = Broker(store)
        t_import = time.perf_counter()
        pool.register(controller)
        t_built = time.perf_counter()
        setup = {"import_s": t_import - T_START,
                 "build_s": t_built - T_START,
                 "build_wait_s": t_built - t_import}
        faulthandler.dump_traceback_later(WATCHDOG_S, exit=False)
        setup.update(warm_up(broker, workload, compiles))
        compiles_before = compiles["requests"]
        hits_before = BROKER_METRICS.meter_count(
            BrokerMeter.RESULT_CACHE_HITS)
        trace_prefix = "SET trace = true; " if args.trace else ""
        gc_pauses = watch_gc() if args.trace else []
        sequences = [workload.client_sequence(i)
                     for i in range(mix["clients"])]
        setup_s = time.perf_counter() - T_START
        records, t0, hung, sliced = drive(
            broker, sequences, args.seconds, trace_prefix,
            mix["trace_slice"] if args.trace else None, trace_dir)
        faulthandler.cancel_dump_traceback_later()
        window_compiles = compiles["requests"] - compiles_before
        cache_hits = BROKER_METRICS.meter_count(
            BrokerMeter.RESULT_CACHE_HITS) - hits_before
        free_at_close = host_memory_free()
        gc_pauses = list(gc_pauses)  # later collections are not the window's
        events = fallback_events()
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        device["memory_peak_bytes"] = max(
            (s.get("peak_bytes_in_use", 0) for s in stats), default=0)
        say(f"window closed: {len(records)} requests, {cache_hits} from the "
            f"result cache, set-up {setup_s:.1f}s "
            f"{ {k: round(v, 2) for k, v in setup.items()} }")
        server.stop()
        server = None
        shutil.rmtree(data_dir, ignore_errors=True)

        trace = None
        if args.trace and not args.rehearse:
            import trace_reduce

            trace = trace_reduce.reduce_planes(
                trace_reduce.read_xplane(trace_dir))
            # the traced window is the device tracer's, first operation to
            # last: busy time and window are then taken over the same time
            # (the host's slice lies inside it, a few ms shorter)
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["traced_s"]
            device["slice_s"] = sliced[1] - sliced[0]

        # the reference runs last: the window is closed, the peak is read,
        # the server is stopped; its time is in no metric
        t_ref = time.perf_counter()
        want = reference_answers(cell, args.seed, rows_per_segment, records)
        verdict = compare(records, workload, want)
        say(f"reference + comparison took "
            f"{time.perf_counter() - t_ref:.1f}s; host memory free "
            f"{free_at_close} at the window's close, "
            f"{host_memory_free()} now")
        failed = sum(1 for r in records if r.error) + len(hung)
        compared = {
            "wrong_answers": {"value": verdict["wrong"], "limit": 0},
            "failed_requests": {"value": failed, "limit": 0},
            "fallback_events": {"value": sum(events.values()), "limit": 0},
            "window_compiles": {"value": window_compiles, "limit": 0},
        }
        correct = bool(records) and all(
            c["value"] <= c["limit"] for c in compared.values())
        run = types.SimpleNamespace(
            records=records, slice=sliced, trace=trace, config=config,
            total_rows=total_rows, rows_per_segment=rows_per_segment,
            setup=setup, workload=workload, seed=args.seed, cell=cell,
            want=want, trace_dir=trace_dir,
            peak=peaks.get(device["kind"]), t0=t0, gc_pauses=gc_pauses)
        metrics = {}
        if correct and not args.rehearse:
            if args.trace:
                for m in cell_metrics(bench, cell["name"], "per_layer"):
                    spec = traffic.load("metrics", m["name"])
                    reader = module_at(HERE / "readers"
                                       / f"{spec['reader']}.py")
                    value = reader.read(run, spec["params"])
                    if value is not None:
                        metrics[m["name"]] = {"value": value,
                                              "unit": m["unit"]}
            else:
                values = end_to_end(records, t0, setup_s)
                for m in cell_metrics(bench, cell["name"], "end_to_end"):
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
        result = {"correct": correct, "attempted": len(records) + len(hung),
                  "failed": failed, "metrics": metrics, "device": device}
        if trace is not None and correct:
            import xplane

            spans = xplane.trace(trace_dir)
            result["breakdown"] = {
                "device_ops": trace["device_modules"] or trace["device_ops"],
                "idle_gaps": xplane.idle_gaps(
                    spans["host"], spans["modules"], spans["ops"])}
        by_class = {}
        for r in records:
            by_class.setdefault(r.cls, []).append((r.end - r.start) * 1e3)
        result["traffic"] = {
            "result_cache_hits": cache_hits,
            "requests_by_class": {c: len(v) for c, v in by_class.items()},
            "median_ms_by_class": {c: statistics.median(v)
                                   for c, v in by_class.items()}}
        if args.rehearse:
            result["rehearsal"] = "CPU at toy size: no metric is printed"
        if after is not None:
            result.update(after(run))
        result["compared"] = compared
        if verdict["first"]:
            say(f"first wrong answer: {verdict['first']}")
        if any(events.values()):
            say(f"fallback events in this process: {events}")
        for name, c in compared.items():
            say(f"compared {name} = {c['value']} (limit {c['limit']})")
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
        return 0 if correct else 1
    finally:
        faulthandler.cancel_dump_traceback_later()
        pool.close()
        if server is not None:
            try:
                server.stop()
            except Exception as e:  # the verdict matters more
                say(f"server.stop() failed: {e!r}")
        shutil.rmtree(data_dir, ignore_errors=True)
        shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None, after=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on the CPU (JAX_PLATFORMS=cpu), Pallas "
                         "in interpret mode; never prints a metric")
    args = ap.parse_args(argv)
    try:
        return run_cell(args, after)
    except BenchFailure as e:
        say(f"FAILED: {e}")
        return 1


if __name__ == "__main__":
    # daemon threads (rpc accept loops, periodic tasks) must not keep a
    # finished run alive
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
