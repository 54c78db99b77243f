"""Server role: converge to ideal state, host segments, serve queries.

Reference analogue: pinot-server — BaseServerStarter.start:578 boots the
instance data manager + query executor + Netty server and joins Helix; the
state model SegmentOnlineOfflineStateModelFactory.java:44 handles
OFFLINE→ONLINE (load segment), ONLINE→OFFLINE (release), →DROPPED
transitions (:73-140). Here the transitions are driven by a watch on the
ideal state; after each transition the server updates the external view,
exactly Helix's contract.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
from typing import Optional

from ..engine.query_executor import QueryExecutor
from ..segment.loader import SegmentIntegrityError, load_segment
from ..spi import faults
from ..spi.data_types import Schema
from ..spi.metrics import SERVER_METRICS, ServerMeter, ServerTimer
from ..spi.trace import (TRACING, ServerQueryPhase, sample_decision,
                         trace_sample_rate)
from ..storage.tier import SegmentTierManager
from .controller import ERROR, ONLINE, raw_table_name
from .store import PropertyStore
from ..engine.scheduler import QueryScheduler
from .transport import RpcServer

log = logging.getLogger(__name__)


def _quantile(sorted_ms: list, q: float) -> float:
    """Nearest-rank quantile over an already-sorted latency sample."""
    if not sorted_ms:
        return 0.0
    idx = min(len(sorted_ms) - 1, int(q * len(sorted_ms)))
    return round(float(sorted_ms[idx]), 3)


def _safe_mesh_devices() -> int:
    """meshDevices gauge supplier: local chips the segment mesh may span
    (1 when the device backend is unavailable at scrape time)."""
    try:
        from ..parallel.mesh import mesh_device_count

        return mesh_device_count()
    except Exception:
        return 1


class ServerInstance:
    def __init__(self, store: PropertyStore, instance_id: str,
                 backend: str = "auto", tags: Optional[list[str]] = None,
                 max_concurrent_queries: int = 8,
                 local_storage_mb: Optional[float] = None):
        self.store = store
        self.instance_id = instance_id
        self.tags = tags or ["DefaultTenant"]
        self.backend = backend
        self.executor = QueryExecutor(backend=backend)
        # tiered storage: the byte-budgeted local disk tier beneath the HBM
        # plane cache. Every locally materialized segment directory —
        # converge load, cold lazy load, repair/rebalance re-fetch — goes
        # through tier.acquire(), so ONE budget accounts for all of them.
        # ``local_storage_mb`` overrides PINOT_TPU_LOCAL_STORAGE_MB.
        tier_kwargs = {}
        if local_storage_mb is not None:
            tier_kwargs["budget_mb"] = local_storage_mb
        self._tier = SegmentTierManager(
            instance_id=instance_id, evict_cb=self._evict_segment,
            heat_fn=self._broker_table_costs, **tier_kwargs)
        # cold (metadata-only) segments: advertised ONLINE but not local —
        # tableNameWithType → {segment_name: /SEGMENTS meta dict}
        self._cold: dict[str, dict[str, dict]] = {}
        # catalog meta of RESIDENT segments, kept so eviction can demote
        # them back to cold without a store read
        self._seg_meta: dict[tuple, dict] = {}
        # in-flight cold warms: (table, seg) → completion Event, so
        # concurrent queries coalesce on one fetch instead of racing
        self._warming: dict[tuple, threading.Event] = {}
        # admission control in front of execution (reference:
        # QueryScheduler.submit, fcfs default policy)
        self.scheduler = QueryScheduler(max_concurrent=max_concurrent_queries)
        # tableNameWithType → {segment_name: ImmutableSegment}
        self.segments: dict[str, dict[str, object]] = {}
        # integrity quarantine: tableNameWithType → {segment_name → entry}
        # (a replica that failed load-verify; advertised ERROR, never
        # routed, owned by the repair path until it re-verifies)
        self.quarantined: dict[str, dict[str, dict]] = {}
        # transient (non-integrity) load failures: (table, seg) → attempts;
        # bounded so one flaky deep-store read doesn't loop a converge hot,
        # reset by a repair nudge, a successful load, or a drop
        self._load_failures: dict[tuple, int] = {}
        self.max_load_retries = int(
            os.environ.get("PINOT_TPU_LOAD_RETRIES", "5"))
        self._lock = threading.RLock()
        self._rpc = RpcServer(self._handle)
        # compile/HBM telemetry: supplier gauges polled only at /metrics
        # scrape time (spi/metrics.py evaluates suppliers in snapshot()),
        # so the dispatch hot path never pays for them
        from ..engine.compile_registry import COMPILE_REGISTRY
        from ..segment.device_cache import GLOBAL_DEVICE_CACHE
        from ..spi.metrics import ServerGauge

        SERVER_METRICS.set_gauge(
            ServerGauge.COMPILE_FAMILIES,
            lambda: COMPILE_REGISTRY.totals()["families"])
        SERVER_METRICS.set_gauge(
            ServerGauge.COMPILE_MS_TOTAL,
            lambda: COMPILE_REGISTRY.totals()["compileMs"])
        SERVER_METRICS.set_gauge(
            ServerGauge.HBM_BYTES_USED,
            lambda: GLOBAL_DEVICE_CACHE.hbm_telemetry()["bytesUsed"])
        SERVER_METRICS.set_gauge(
            ServerGauge.HBM_BYTES_HIGH_WATER,
            lambda: GLOBAL_DEVICE_CACHE.hbm_telemetry()["highWater"]["total"])
        SERVER_METRICS.set_gauge(
            ServerGauge.HBM_EVICTIONS,
            lambda: GLOBAL_DEVICE_CACHE.hbm_telemetry()["evictions"])
        # mesh execution telemetry: how many local chips the segment-axis
        # mesh spans, plus per-device HBM residency (one dynamic gauge per
        # device id — scrape-time shard walks, never on the query path)
        if backend != "host":
            SERVER_METRICS.set_gauge(ServerGauge.MESH_DEVICES,
                                     _safe_mesh_devices)
            try:
                import jax

                for d in jax.devices():
                    SERVER_METRICS.set_gauge(
                        f"hbmBytesUsedDevice.{d.id}",
                        lambda did=int(d.id):
                        GLOBAL_DEVICE_CACHE.hbm_per_device().get(did, 0))
            except Exception:
                pass
        self._started = False
        # readiness (GET /health/readiness) gates on the FIRST converge
        # pass completing, not on mere registration: a server that joined
        # but has not loaded its ideal-state segments would answer queries
        # with missing-segment errors
        self._converged = False
        # per-INSTANCE wall-ms of recent query RPCs — the straggler signal
        # for the controller's ClusterHealthChecker (the metrics-registry
        # timers are process-wide singletons, indistinguishable between
        # co-hosted instances)
        self._query_ms: deque = deque(maxlen=256)

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> None:
        self.store.set(f"/INSTANCECONFIGS/{self.instance_id}",
                       {"host": self._rpc.host, "port": self._rpc.port,
                        "tags": self.tags})
        self.store.set(f"/LIVEINSTANCES/{self.instance_id}",
                       {"host": self._rpc.host, "port": self._rpc.port},
                       ephemeral_owner=self.instance_id)
        self.store.watch("/IDEALSTATES/", self._on_ideal_state)
        self.store.watch("/REPAIRS/", self._on_repair_request)
        self.store.watch("/PREFETCH/", self._on_prefetch)
        self._started = True
        # replay current ideal states (Helix replays pending transitions on join)
        for table in self.store.children("/IDEALSTATES"):
            self._converge(table, self.store.get(f"/IDEALSTATES/{table}"))
        self._converged = True

    def stop(self) -> None:
        """Simulates process death: ephemeral live-instance entry expires.
        Instance config stays (reference: ZK session expiry vs config)."""
        self._started = False
        self._converged = False
        self._rpc.close()
        # unregister the ideal-state watcher: a dead server left in the
        # store's watch list is pinned alive with every loaded segment's
        # memmap fd — unbounded fd/memory growth under server churn
        try:
            self.store.unwatch(self._on_ideal_state)
            self.store.unwatch(self._on_repair_request)
            self.store.unwatch(self._on_prefetch)
        except AttributeError:
            pass  # store impls without unwatch (older remote protocol)
        self.store.expire_session(self.instance_id)
        # release every tier-local copy (also cleans the work dirs the old
        # per-instance untar/repair tempdirs used to leak)
        self._tier.close()

    @property
    def address(self) -> tuple[str, int]:
        return (self._rpc.host, self._rpc.port)

    # -- state transitions --------------------------------------------------
    def _on_ideal_state(self, path: str, value) -> None:
        if not self._started:
            return
        table = path.rsplit("/", 1)[-1]
        self._converge(table, value)

    def _converge(self, table: str, ideal: Optional[dict]) -> None:
        """Diff ideal vs hosted → load/drop (the OFFLINE→ONLINE / →DROPPED
        transitions)."""
        ideal = ideal or {}
        want = {seg for seg, m in ideal.items()
                if m.get(self.instance_id) == ONLINE}
        with self._lock:
            have = set(self.segments.get(table, {}))
            to_load = want - have
            to_drop = have - want
            indexing = None
            if to_load:
                cfg_json = self.store.get(f"/CONFIGS/TABLE/{table}")
                if cfg_json and "tableName" in cfg_json:
                    from ..spi.table_config import TableConfig

                    indexing = TableConfig.from_json(cfg_json).indexing
            repair_kicks = []
            for seg in to_load:
                meta = self.store.get(f"/SEGMENTS/{table}/{seg}")
                if meta is None:
                    continue
                if seg in self.quarantined.get(table, {}):
                    # the local copy failed verification — reloading it
                    # would just fail again; the repair path owns it until
                    # a fresh deep-store fetch verifies
                    continue
                if self._load_failures.get((table, seg), 0) \
                        >= self.max_load_retries:
                    continue  # transient retries exhausted; needs a nudge
                if self._tier.should_lazy_load():
                    # the local tier is at budget: register the segment
                    # COLD — metadata only, no fetch. It still advertises
                    # ONLINE below; the first query that routes here (or a
                    # prefetch nudge) warms it lazily
                    self._cold.setdefault(table, {})[seg] = meta
                    continue
                try:
                    segment = self._load_segment_verified(
                        table, seg, meta, indexing)
                except SegmentIntegrityError as e:
                    # integrity failure: quarantine (ERROR in the external
                    # view, excluded from routing) and hand off to repair
                    self._quarantine(table, seg, e)
                    repair_kicks.append(seg)
                    continue
                except Exception:
                    # a failed load must not abort convergence of the other
                    # segments — and since the external-view update below
                    # advertises only want & loaded, the broker routes this
                    # segment's replicas elsewhere (or reports it partial).
                    # Transient (non-integrity) failures retry on the NEXT
                    # converge, bounded by max_load_retries.
                    n = self._load_failures.get((table, seg), 0) + 1
                    self._load_failures[(table, seg)] = n
                    log.exception("%s: failed to load segment %s/%s "
                                  "(attempt %d/%d)", self.instance_id, table,
                                  seg, n, self.max_load_retries)
                    continue
                self.segments.setdefault(table, {})[seg] = segment
                self._seg_meta[(table, seg)] = meta
                self._cold.get(table, {}).pop(seg, None)
                self._load_failures.pop((table, seg), None)
            cold_tbl = self._cold.get(table, {})
            cold_drop = set(cold_tbl) - want
            if to_drop or cold_drop:
                # dropped/replaced segments invalidate their cached partial
                # results (host + device tiers) and release device planes —
                # the server-side half of lineage-driven invalidation
                from ..cache.partial import GLOBAL_PARTIAL_CACHE
                from ..segment.device_cache import GLOBAL_DEVICE_CACHE
            for seg in cold_drop:
                # a departed cold segment has no local bytes or live object,
                # but name-keyed HBM leftovers from its resident days and
                # journaled partials must still go
                cold_tbl.pop(seg, None)
                GLOBAL_PARTIAL_CACHE.invalidate_segment(seg)
                GLOBAL_DEVICE_CACHE.drop_partials(segment_name=seg)
                GLOBAL_DEVICE_CACHE.drop_named(seg)
            for seg in to_drop:
                segment = self.segments.get(table, {}).pop(seg, None)
                self._seg_meta.pop((table, seg), None)
                self._tier.forget(table, seg)
                GLOBAL_PARTIAL_CACHE.invalidate_segment(seg)
                GLOBAL_DEVICE_CACHE.drop_partials(segment_name=seg)
                if segment is not None:
                    GLOBAL_DEVICE_CACHE.drop(segment)
                else:
                    # the live object is gone (lost mid-move, repair window,
                    # prior incarnation of this instance) — id()-keyed views
                    # and stacked [S, N] batch-family planes can only be
                    # found by NAME now, and left behind they pin HBM for a
                    # segment this server no longer serves
                    GLOBAL_DEVICE_CACHE.drop_named(seg)
            # segments dropped from the ideal state release their quarantine
            # entry and transient-failure counters — nothing left to repair
            for seg in set(self.quarantined.get(table, ())) - want:
                self.quarantined[table].pop(seg, None)
            for key in [k for k in self._load_failures
                        if k[0] == table and k[1] not in want]:
                self._load_failures.pop(key, None)
            self._register_table(table)
            loaded = set(self.segments.get(table, {}))
            cold = set(self._cold.get(table, ()))
        # advertise what actually loaded PLUS the cold (metadata-only)
        # registrations — a cold replica is still routable (the first query
        # warms it); a skipped/FAILED load must not appear ONLINE or the
        # broker would silently lose its rows
        self._update_external_view(table, (want & loaded) | (want & cold))
        for seg in repair_kicks:
            self._kick_repair(table, seg)

    def _fetch(self, location: str, fresh: bool = False,
               table: str = "", seg: str = "") -> str:
        """Deep-store fetch THROUGH the storage tier: tarred segments
        download + untar into the SegmentTierManager's byte-budgeted local
        cache (reference: SegmentFetcherFactory on OFFLINE→ONLINE), so
        converge loads, cold lazy loads and repair/rebalance re-fetches all
        draw from one budget; plain directories load in place. ``fresh``
        fetches a new copy so a repair never reuses a possibly-damaged
        local one. The returned path carries one reader ref (``hold``) so
        a concurrent acquire's eviction pass cannot reclaim the directory
        before the loader has read it; the caller drops it via
        ``tier.release()`` once the segment is loaded."""
        if not seg:
            seg = os.path.basename(str(location))
        return self._tier.acquire(table or "_unassigned", seg, location,
                                  fresh=fresh, hold=True)

    def _load_segment_verified(self, table: str, seg: str, meta: dict,
                               indexing, fresh: bool = False,
                               cold: bool = False):
        """Fetch + load + verify one segment. The ``segment.load`` fault
        point fires here; an injected ``corrupt`` fault damages a local COPY
        of the fetched directory (the deep store stays pristine, so repair
        can heal) and the verifying loader is expected to catch it. Cold
        lazy loads additionally pass through the ``storage.fetch`` point
        with the same corrupt→quarantine→repair-fresh contract as
        ``rebalance.move``."""
        corruption = None
        if faults.ACTIVE:
            try:
                faults.FAULTS.fire("segment.load", table=table, segment=seg)
            except faults.InjectedCorruption as c:
                corruption = c
            if corruption is None and cold:
                try:
                    faults.FAULTS.fire("storage.fetch", table=table,
                                       segment=seg,
                                       instance=self.instance_id)
                except faults.InjectedCorruption as c:
                    corruption = c
            if corruption is None and self._is_move_destination(table, seg):
                # chaos seam for mid-rebalance failure: this load is the
                # DESTINATION fetch of an in-flight segment move (the
                # /REBALANCE journal names this instance as the target)
                try:
                    faults.FAULTS.fire("rebalance.move", table=table,
                                       segment=seg,
                                       instance=self.instance_id)
                except faults.InjectedCorruption as c:
                    corruption = c
        local = self._fetch(meta["location"], fresh=fresh,
                            table=table, seg=seg)
        try:
            if corruption is not None:
                local = self._corrupt_local_copy(local, corruption)
            segment = load_segment(local, expected_crc=meta.get("crc"))
            if indexing is not None:
                # config-requested indexes the segment was written
                # without get built at load (SegmentPreProcessor)
                segment.backfill_indexes(indexing)
        finally:
            self._tier.release(table or "_unassigned", seg)
        return segment

    def _is_move_destination(self, table: str, seg: str) -> bool:
        """True when an active rebalance move targets (table, seg) AT this
        instance — consulted only under faults.ACTIVE, so the extra store
        read never taxes a normal load."""
        try:
            job = self.store.get(f"/REBALANCE/{table}")
        except Exception:
            return False
        if not job or job.get("status") not in ("IN_PROGRESS", "ABORTING"):
            return False
        for move in (job.get("movePlan") or []):
            if move.get("segment") == seg \
                    and self.instance_id in (move.get("adds") or {}) \
                    and move.get("state") in ("PENDING", "ADDING"):
                return True
        return False

    def _corrupt_local_copy(self, local: str, c) -> str:
        """Copy the fetched segment dir and damage the copy's data file —
        models on-disk/local-FS corruption without touching the source."""
        import shutil
        import tempfile
        from pathlib import Path

        from ..segment.format import DATA_FILE

        src = Path(local)
        dst = Path(tempfile.mkdtemp(
            prefix=f"{self.instance_id}_corrupt_")) / src.name
        shutil.copytree(src, dst)
        data = dst / DATA_FILE
        data.write_bytes(faults.corrupt_bytes(
            data.read_bytes(), c.mode, c.seed, c.index))
        return str(dst)

    # -- integrity quarantine + repair --------------------------------------
    def _quarantine(self, table: str, seg: str, err) -> None:
        """Record an integrity failure: the replica is advertised ERROR
        (excluded from broker routing) with the reason kept for
        /debug/segments, and the repair path takes ownership."""
        entry = {
            "reason": str(err),
            "columns": list(getattr(err, "columns", []) or []),
            "sinceMs": int(time.time() * 1000),
            "repairAttempts": 0,
            "unrepairable": False,
        }
        with self._lock:
            self.quarantined.setdefault(table, {})[seg] = entry
        SERVER_METRICS.add_meter(ServerMeter.SEGMENTS_QUARANTINED)
        log.error("%s: quarantined segment %s/%s: %s",
                  self.instance_id, table, seg, err)

    def _kick_repair(self, table: str, seg: str) -> None:
        """Schedule a background repair unless auto-repair is disabled
        (tests disable it to drive repair deterministically)."""
        if os.environ.get("PINOT_TPU_AUTO_REPAIR", "true").lower() \
                in ("false", "0", "off", "no"):
            return
        threading.Thread(target=self.repair_segment, args=(table, seg),
                         daemon=True, name=f"repair-{seg}").start()

    def repair_segment(self, table: str, seg: str) -> bool:
        """Self-repair a quarantined segment: re-fetch a FRESH copy from
        deep store, re-verify, and rejoin the external view. Bounded
        retries with exponential backoff (PINOT_TPU_REPAIR_RETRIES /
        PINOT_TPU_REPAIR_BACKOFF_MS); exhaustion flags the replica
        unrepairable so the controller's SegmentIntegrityChecker can
        surface it instead of re-nudging forever."""
        retries = max(1, int(os.environ.get("PINOT_TPU_REPAIR_RETRIES", "3")))
        backoff_s = float(
            os.environ.get("PINOT_TPU_REPAIR_BACKOFF_MS", "50")) / 1000.0
        for attempt in range(retries):
            if attempt:
                time.sleep(min(backoff_s * (2 ** (attempt - 1)), 2.0))
            meta = self.store.get(f"/SEGMENTS/{table}/{seg}")
            ideal = self.store.get(f"/IDEALSTATES/{table}") or {}
            assigned = (ideal.get(seg) or {}).get(self.instance_id) == ONLINE
            if meta is None or not assigned:
                # dropped or moved away while quarantined — nothing to heal
                with self._lock:
                    self.quarantined.get(table, {}).pop(seg, None)
                return False
            indexing = None
            cfg_json = self.store.get(f"/CONFIGS/TABLE/{table}")
            if cfg_json and "tableName" in cfg_json:
                from ..spi.table_config import TableConfig

                indexing = TableConfig.from_json(cfg_json).indexing
            with self._lock:
                ent = self.quarantined.get(table, {}).get(seg)
                if ent is not None:
                    ent["repairAttempts"] += 1
            try:
                segment = self._load_segment_verified(
                    table, seg, meta, indexing, fresh=True)
            except Exception as e:
                log.warning("%s: repair attempt %d/%d for %s/%s failed: %s",
                            self.instance_id, attempt + 1, retries, table,
                            seg, e)
                continue
            with self._lock:
                self.segments.setdefault(table, {})[seg] = segment
                self._seg_meta[(table, seg)] = meta
                self._cold.get(table, {}).pop(seg, None)
                self.quarantined.get(table, {}).pop(seg, None)
                self._load_failures.pop((table, seg), None)
                self._register_table(table)
                want = {s for s, m in ideal.items()
                        if m.get(self.instance_id) == ONLINE}
                online = (want & set(self.segments.get(table, {}))) \
                    | (want & set(self._cold.get(table, ())))
            SERVER_METRICS.add_meter(ServerMeter.SEGMENT_REPAIRS)
            self._update_external_view(table, online)
            log.info("%s: repaired segment %s/%s from deep store "
                     "(attempt %d)", self.instance_id, table, seg,
                     attempt + 1)
            return True
        with self._lock:
            ent = self.quarantined.get(table, {}).get(seg)
            if ent is not None:
                ent["unrepairable"] = True
        log.error("%s: segment %s/%s unrepairable after %d attempts",
                  self.instance_id, table, seg, retries)
        return False

    def _on_repair_request(self, path: str, value) -> None:
        """Controller nudge via /REPAIRS/{table}/{seg} (the
        SegmentIntegrityChecker noticed degraded replication): retry a
        quarantined replica's repair — synchronously, and even when
        auto-repair is off, because an explicit nudge IS the operator
        asking — or re-converge a transient failure whose bounded retries
        were exhausted."""
        if not self._started or value is None:
            return
        parts = path.strip("/").split("/")
        if len(parts) != 3:
            return
        _, table, seg = parts
        with self._lock:
            ent = self.quarantined.get(table, {}).get(seg)
            if ent is not None:
                ent["unrepairable"] = False
            self._load_failures.pop((table, seg), None)
        if ent is not None:
            self.repair_segment(table, seg)
        else:
            self._converge(table, self.store.get(f"/IDEALSTATES/{table}"))

    # -- tiered storage: evict / warm / prefetch -----------------------------
    def _evict_segment(self, table: str, seg: str):
        """Tier evict callback: demote a resident segment to cold
        (metadata-only) state under budget pressure. The deep-store bytes
        are unchanged, so this must NOT bump /CACHEEPOCH and does not touch
        the external view — the replica stays ONLINE and re-fetchable.
        HBM stacks/partials for the departed copy drop by name (the PR-14
        departure hygiene path). Returns the live ImmutableSegment so the
        tier can defer destroy() until in-flight readers drain."""
        from ..cache.partial import GLOBAL_PARTIAL_CACHE
        from ..segment.device_cache import GLOBAL_DEVICE_CACHE

        with self._lock:
            segment = self.segments.get(table, {}).pop(seg, None)
            meta = self._seg_meta.pop((table, seg), None)
            if meta is not None:
                self._cold.setdefault(table, {})[seg] = meta
            if segment is not None:
                self._register_table(table)
        GLOBAL_PARTIAL_CACHE.invalidate_segment(seg)
        GLOBAL_DEVICE_CACHE.drop_partials(segment_name=seg)
        if segment is not None:
            GLOBAL_DEVICE_CACHE.drop(segment)
        GLOBAL_DEVICE_CACHE.drop_named(seg)
        SERVER_METRICS.add_meter(ServerMeter.SEGMENT_EVICTIONS)
        log.info("%s: evicted segment %s/%s to cold (metadata-only)",
                 self.instance_id, table, seg)
        return segment

    def _broker_table_costs(self) -> dict:
        """Fleet-wide decayed per-table query cost from the broker
        /BROKERSTATE beacons (PR-10 WorkloadTracker) — the tier's eviction
        heat weighting. Consulted only when the tier must evict, never on
        the query path."""
        costs: dict[str, float] = {}
        try:
            ids = self.store.children("/BROKERSTATE")
        except Exception:
            return costs
        for bid in ids:
            state = self.store.get(f"/BROKERSTATE/{bid}") or {}
            for t, c in (state.get("tableCostsMs") or {}).items():
                try:
                    costs[t] = max(costs.get(t, 0.0), float(c))
                except (TypeError, ValueError):
                    continue
        # beacons carry broker-facing table names; tier entries are keyed
        # by the type-suffixed internal name — project costs onto both
        for nwt in self._tables_named(list(costs)):
            raw = nwt.rsplit("_", 1)[0]
            if raw in costs:
                costs[nwt] = max(costs.get(nwt, 0.0), costs[raw])
        return costs

    def _tables_named(self, names) -> list:
        """Hosted (resident or cold) internal table names matching any of
        the given broker-facing names — either exactly or modulo the
        ``_OFFLINE``/``_REALTIME`` type suffix."""
        wanted = set(names)
        with self._lock:
            hosted = set(self.segments) | set(self._cold)
        return sorted(t for t in hosted
                      if t in wanted or t.rsplit("_", 1)[0] in wanted)

    def _kick_warm(self, table: str, seg: str) -> threading.Event:
        """Start (or join) a background warm of one cold segment. Returns
        the completion event; concurrent callers coalesce on one fetch."""
        key = (table, seg)
        with self._lock:
            if seg in self.segments.get(table, {}):
                done = threading.Event()
                done.set()
                return done
            ev = self._warming.get(key)
            if ev is not None:
                return ev
            ev = self._warming[key] = threading.Event()
        threading.Thread(target=self._warm_leader, args=(table, seg, ev),
                         daemon=True, name=f"warm-{seg}").start()
        return ev

    def _warm_leader(self, table: str, seg: str, ev: threading.Event) -> None:
        """Fetch + verify + load one cold segment (the single in-flight
        warm for its (table, seg) key). An integrity failure quarantines
        and kicks repair — exactly the rebalance.move contract — so a
        corrupt deep-store fetch heals with a fresh copy instead of being
        served or retried in place."""
        t0 = time.perf_counter()
        try:
            with self._lock:
                meta = self._cold.get(table, {}).get(seg)
            if meta is None:
                return
            indexing = None
            cfg_json = self.store.get(f"/CONFIGS/TABLE/{table}")
            if cfg_json and "tableName" in cfg_json:
                from ..spi.table_config import TableConfig

                indexing = TableConfig.from_json(cfg_json).indexing
            try:
                segment = self._load_segment_verified(
                    table, seg, meta, indexing, cold=True)
            except SegmentIntegrityError as e:
                with self._lock:
                    self._cold.get(table, {}).pop(seg, None)
                self._quarantine(table, seg, e)
                self._kick_repair(table, seg)
                return
            except Exception:
                with self._lock:
                    n = self._load_failures.get((table, seg), 0) + 1
                    self._load_failures[(table, seg)] = n
                log.warning("%s: cold load of %s/%s failed (attempt %d)",
                            self.instance_id, table, seg, n, exc_info=True)
                return
            with self._lock:
                self._cold.get(table, {}).pop(seg, None)
                self.segments.setdefault(table, {})[seg] = segment
                self._seg_meta[(table, seg)] = meta
                self._load_failures.pop((table, seg), None)
                self._register_table(table)
            SERVER_METRICS.add_meter(ServerMeter.SEGMENT_COLD_LOADS)
            SERVER_METRICS.update_timer(
                ServerTimer.COLD_LOAD_MS, (time.perf_counter() - t0) * 1000.0)
        finally:
            with self._lock:
                self._warming.pop((table, seg), None)
            ev.set()

    def _warm_cold_segments(self, table: str, cold_names: list,
                            deadline_ms) -> list:
        """Deadline-aware lazy warm of cold routed segments: kick all the
        warms, then wait for each inside the remaining broker budget minus
        a floor. Returns the names still cold when the budget ran out —
        they keep warming in the background (next query finds them
        resident) while THIS response degrades instead of blocking."""
        floor_s = float(
            os.environ.get("PINOT_TPU_COLD_SYNC_FLOOR_MS", "25")) / 1000.0
        deadline = None
        if deadline_ms is not None:
            deadline = time.monotonic() + max(0.0, float(deadline_ms) / 1000.0)
        events = [(seg, self._kick_warm(table, seg)) for seg in cold_names]
        still = []
        for seg, ev in events:
            timeout = None
            if deadline is not None:
                timeout = max(0.0, deadline - time.monotonic() - floor_s)
            ev.wait(timeout)
            with self._lock:
                if seg not in self.segments.get(table, {}):
                    still.append(seg)
        return still

    def _on_prefetch(self, path: str, value) -> None:
        """/PREFETCH/{table} nudge from the leader's StoragePrefetcher:
        mark the table hot (goes last in eviction order for the hot TTL)
        and warm its cold segments in the background while tier headroom
        remains, so the hot table is resident before traffic lands."""
        if not self._started or value is None:
            return
        parts = path.strip("/").split("/")
        if len(parts) != 2:
            return
        # the nudge names the broker-facing table; hosted state is keyed
        # by the type-suffixed internal name
        table = parts[1]
        self._tier.note_hot(table)
        for nwt in self._tables_named([table]):
            self._tier.note_hot(nwt)
            with self._lock:
                cold = sorted(self._cold.get(nwt, {}))
            if cold:
                threading.Thread(target=self._prefetch_warm,
                                 args=(nwt, cold),
                                 daemon=True,
                                 name=f"prefetch-{nwt}").start()
        # the same nudge pre-warms the table's AOT-persisted executables:
        # the prefetcher predicts traffic is about to land here, so deserialize
        # its top family programs off the serving path (engine/aot_cache.py)
        from ..engine.aot_cache import enabled as _aot_enabled, prewarm_table
        if _aot_enabled():
            threading.Thread(target=prewarm_table, args=(table,),
                             daemon=True,
                             name=f"aot-prewarm-{table}").start()

    def _prefetch_warm(self, table: str, names: list) -> None:
        for seg in names:
            if not self._started or not self._tier.headroom():
                return  # prefetch warms fill headroom; they never evict
            self._kick_warm(table, seg).wait(30.0)
            with self._lock:
                ok = seg in self.segments.get(table, {})
            if ok:
                SERVER_METRICS.add_meter(ServerMeter.PREFETCH_HITS)

    def debug_storage(self) -> dict:
        """Storage-tier inventory for GET /debug/storage: local-tier
        budget/usage, resident vs cold (metadata-only) segments per table,
        and the in-flight warm queue."""
        with self._lock:
            tables = sorted(set(self.segments) | set(self._cold))
            per_table = {
                t: {"resident": sorted(self.segments.get(t, {})),
                    "cold": sorted(self._cold.get(t, {}))}
                for t in tables}
            warming = sorted(f"{t}/{s}" for t, s in self._warming)
        return {
            "localTier": self._tier.stats(),
            "residentSegments": sum(len(v["resident"])
                                    for v in per_table.values()),
            "coldSegments": sum(len(v["cold"]) for v in per_table.values()),
            "warming": warming,
            "tables": per_table,
        }

    def health_status(self) -> dict:
        """Per-instance health beacon: answered over RPC (`status`) to the
        controller's ClusterHealthChecker and over GET /debug/status. Reads
        only instance-local state plus the process metric singletons — no
        device syncs, no query-path locks beyond the instance lock."""
        from ..segment.device_cache import GLOBAL_DEVICE_CACHE

        lat = sorted(self._query_ms)
        with self._lock:
            quarantined = {t: sorted(q) for t, q in self.quarantined.items()
                           if q}
            num_segments = sum(len(s) for s in self.segments.values())
            num_docs = sum(int(getattr(seg, "num_docs", 0))
                           for table in self.segments.values()
                           for seg in table.values())
        return {
            "instanceId": self.instance_id,
            "started": self._started,
            "converged": self._converged,
            "queryLatencyMs": {
                "count": len(lat),
                "p50": _quantile(lat, 0.50),
                "p95": _quantile(lat, 0.95),
                "p99": _quantile(lat, 0.99),
            },
            "hbm": GLOBAL_DEVICE_CACHE.hbm_stats(),
            "segmentCache": {
                "hits": SERVER_METRICS.meter_count(
                    ServerMeter.SEGMENT_CACHE_HITS),
                "misses": SERVER_METRICS.meter_count(
                    ServerMeter.SEGMENT_CACHE_MISSES),
            },
            "hbmOomEvents": SERVER_METRICS.meter_count(
                ServerMeter.HBM_OOM_EVENTS),
            "quarantined": quarantined,
            "numSegments": num_segments,
            "numDocs": num_docs,
        }

    def debug_segments(self) -> dict:
        """Hosted-vs-quarantined segment inventory for GET /debug/segments."""
        with self._lock:
            out = {}
            for table in sorted(set(self.segments) | set(self.quarantined)):
                q = self.quarantined.get(table, {})
                out[table] = {
                    "served": sorted(self.segments.get(table, {})),
                    "quarantined": {s: dict(e) for s, e in sorted(q.items())},
                }
            return out

    def _register_table(self, table: str) -> None:
        raw = raw_table_name(table)
        schema_json = self.store.get(f"/SCHEMAS/{raw}")
        if schema_json is None or table not in self.segments:
            return
        schema = Schema.from_json(schema_json)
        segments = list(self.segments[table].values())
        cfg = self.store.get(f"/CONFIGS/TABLE/{table}") or {}
        if cfg.get("warmOnLoad") and self.backend != "host":
            # pre-upload column planes to HBM off the convergence thread
            # (reference: segment preload on load — first query skips H2D)
            import threading as _threading

            from ..segment.device_cache import GLOBAL_DEVICE_CACHE

            def _warm(segs=list(segments)):
                for seg in segs:
                    try:
                        GLOBAL_DEVICE_CACHE.warm(seg)
                    except Exception:
                        return  # no accelerator / transient: queries warm lazily

            _threading.Thread(target=_warm, daemon=True,
                              name=f"warm-{table}").start()
        if cfg.get("isDimTable") and schema.primary_key_columns:
            # dimension table: every server holds the full copy and serves
            # LOOKUP joins from it (reference DimensionTableDataManager)
            self.executor.add_dimension_table(schema, segments, name=table)
            # LOOKUP callers name the RAW table
            from ..engine.dim_tables import alias_dimension_table

            alias_dimension_table(raw, table)
            return
        self.executor.add_table(schema, segments, name=table)

    def _update_external_view(self, table: str, online: set) -> None:
        with self._lock:
            error = set(self.quarantined.get(table, ())) - set(online)

        def upd(view):
            view = view or {}
            for seg in list(view):
                view[seg].pop(self.instance_id, None)
                if not view[seg]:
                    del view[seg]
            for seg in online:
                view.setdefault(seg, {})[self.instance_id] = ONLINE
            # quarantined replicas are advertised ERROR (reference: Helix
            # ERROR state) — visible to the controller's integrity checker,
            # invisible to broker routing (which selects ONLINE only)
            for seg in error:
                view.setdefault(seg, {})[self.instance_id] = ERROR
            return view

        # a glitching control plane (injected store.write fault, CAS
        # contention burst) must not abort convergence: retry briefly, then
        # leave the old advertisement — the next converge republishes
        from .store import StoreError

        for attempt in range(4):
            try:
                self.store.update(f"/EXTERNALVIEW/{table}", upd)
                return
            except (StoreError, faults.InjectedFault):
                if attempt == 3:
                    log.warning("%s: external-view update for %s kept "
                                "failing; serving stale view until next "
                                "converge", self.instance_id, table,
                                exc_info=True)
                else:
                    time.sleep(0.01 * (attempt + 1))

    # -- query plane --------------------------------------------------------
    def _handle(self, request):
        kind = request.get("type")
        if kind == "query":
            t0 = time.perf_counter()
            try:
                return self._handle_query(request)
            finally:
                # timed here (not in _handle_query) so scheduler waits and
                # injected server.query delays both land in the ring — the
                # health checker must see the latency the broker sees
                self._query_ms.append((time.perf_counter() - t0) * 1000.0)
        if kind == "status":
            return self.health_status()
        if kind == "query_stream":
            return self._handle_query_stream(request)
        if kind == "explain":
            return self._handle_explain(request)
        if kind == "scan_arrow":
            return self._handle_scan_arrow(request)
        if kind == "ping":
            return "pong"
        if kind == "cancel":
            # broker abandon/timeout: flag the tracker so the segment loop's
            # check_cancel stops device work (reference: the /query/{id}
            # DELETE path into the accountant interrupt). A prefix cancel
            # kills every shard of the query (`<query_id>:<n>` ids) and
            # tombstones the prefix so a shard that lost the race to this
            # cancel still dies on arrival.
            reason = request.get("reason", "cancelled by broker")
            qid = request.get("queryId", "")
            if request.get("prefix"):
                return {"cancelled": self.scheduler.accountant.kill_prefix(
                    qid, reason=reason) > 0}
            return {"cancelled": self.scheduler.accountant.kill_query(
                qid, reason=reason)}
        if isinstance(kind, str) and kind.startswith("mse_"):
            return self.mse_worker.handle(request)
        raise ValueError(f"unknown request type {kind}")

    @property
    def mse_worker(self):
        """Multi-stage worker endpoint (mse/distributed.py) — lazily built
        so the MSE runtime only loads when a stage is dispatched here.
        Double-checked under the instance lock: stage dispatch and mailbox
        deliveries arrive CONCURRENTLY (pipelined dispatcher), and an
        unlocked first touch can build two services — the losing request's
        blocks land in an orphaned MailboxStore and the query hangs."""
        worker = getattr(self, "_mse_worker", None)
        if worker is None:
            with self._lock:
                worker = getattr(self, "_mse_worker", None)
                if worker is None:
                    from ..mse.distributed import MseWorkerService

                    worker = MseWorkerService(self)
                    self._mse_worker = worker
        return worker

    def _handle_query(self, request):
        """Execute a QueryContext over an explicit segment list (the broker
        names segments per server, reference InstanceRequest.searchSegments)
        under the scheduler's admission control."""
        query = request["query"]
        query_id = request.get("queryId")
        # trace option: the server owns a trace for its shard of the query
        # (scheduler.submit runs the query on this thread, so the
        # thread-local trace covers execute_segments and its family
        # dispatches); the span list rides back next to the datatable for
        # the broker to merge. The trace is named for the broker's queryId
        # (each scatter RPC carries ``<query_id>:<n>``), which the broker
        # and every shard share.
        trace = None
        root_qid = str(query_id).split(":", 1)[0] if query_id else ""
        if TRACING.active_trace() is None and (
                query.query_options.get("trace") in (True, "true", 1)
                # flight-recorder head sampling: hashing the queryId PREFIX
                # makes every shard reach the broker's own sample decision
                # without an option riding the wire
                or (root_qid and sample_decision(root_qid,
                                                 trace_sample_rate()))):
            trace = TRACING.start_trace(
                root_qid or f"server:{self.instance_id}")
        try:
            # the server-side total: entry to the encoded blob in hand
            with TRACING.scope(ServerQueryPhase.QUERY_PROCESSING):
                out = self._process_query(request, query, query_id)
        finally:
            if trace is not None:
                TRACING.end_trace()
        if trace is not None:
            out["trace"] = trace.to_json()
        return out

    def _process_query(self, request, query, query_id):
        table = request["table"]
        names = request["segments"]
        if faults.ACTIVE:
            faults.FAULTS.fire("server.query", table=table,
                               instance=self.instance_id)
        # deadline propagation: the broker stamps its remaining budget on
        # the request; it bounds the scheduler queue wait AND clamps the
        # per-segment loop's timeoutMs (the request is unpickled fresh per
        # RPC, so mutating query_options here is private to this call)
        deadline_ms = request.get("deadlineMs")
        t_enter = time.monotonic()
        # cold (metadata-only) routed segments warm BEFORE admission,
        # bounded by the remaining broker budget; un-warmable ones ride the
        # missing-segments machinery (replica retry → degrade) instead of
        # blocking the response
        with self._lock:
            hosted = self.segments.get(table, {})
            cold_routed = [n for n in names if n not in hosted
                           and n in self._cold.get(table, {})]
        still_cold = self._warm_cold_segments(table, cold_routed,
                                              deadline_ms) \
            if cold_routed else []
        timeout_s = 60.0
        if deadline_ms is not None:
            left_ms = max(50.0, float(deadline_ms)
                          - (time.monotonic() - t_enter) * 1000.0)
            timeout_s = max(0.05, min(60.0, left_ms / 1000.0))
            cur = query.query_options.get("timeoutMs")
            query.query_options["timeoutMs"] = (
                left_ms if cur is None else min(float(cur), left_ms))
        with self._lock:
            hosted = self.segments.get(table, {})
            segs = [hosted[n] for n in names if n in hosted]
            missing = [n for n in names if n not in hosted]
            # refcount-pin the tier-local copies for the scan: an eviction
            # racing this query defers its directory removal (and the
            # segment destroy) until the pin releases — no ENOENT mid-scan
            pins = self._tier.pin(table, [n for n in names if n in hosted])

        def run(tracker):
            return self.executor.execute_segments(query, segs, tracker=tracker)

        try:
            combined, stats = self.scheduler.submit(
                run, group=table, timeout_s=timeout_s, query_id=query_id)
        finally:
            self._tier.unpin(pins)
        stats["missing_segments"] = missing
        if still_cold:
            # names the broker both counts (coldSegmentsWarming) and may
            # retry against this same instance once the warm completes
            stats["cold_segments"] = [n for n in still_cold if n in missing]
        # intermediates travel as the versioned binary DataTable, not as
        # pickled Python objects (reference: DataTableImplV4 on the wire)
        from .datatable import encode

        with TRACING.scope(ServerQueryPhase.RESPONSE_SERIALIZATION):
            blob = encode(combined, stats)
        if faults.ACTIVE:
            # the "datatable.encode" corrupt fault damages the encoded
            # payload — the broker's checksum must catch it downstream
            blob = faults.corrupt_at("datatable.encode", blob, table=table,
                                     instance=self.instance_id)
        return {"datatable": blob}

    def _handle_scan_arrow(self, request):
        """Direct Arrow IPC segment read for external engines — straight
        from segment storage, no SQL/DataTable in the data path
        (reference: the Spark connector's gRPC server reads;
        connectors/arrow_reader.py holds the client half)."""
        from ..connectors.arrow_reader import segment_ipc_bytes

        table = request["table"]
        name = request["segment"]
        with self._lock:
            seg = self.segments.get(table, {}).get(name)
        if seg is None:
            raise ValueError(f"segment {name} not hosted for {table}")
        with self._tier.reading(table, [name]):
            ipc = segment_ipc_bytes(seg, request.get("columns"))
        return {"ipc": ipc, "numRows": seg.num_docs}

    def _handle_explain(self, request):
        """Render the operator-tree plan for this server's hosted segments
        without executing (reference: EXPLAIN runs the plan maker only)."""
        from types import SimpleNamespace

        from ..engine.explain import explain_plan

        table = request["table"]
        names = request["segments"]
        query = request["query"]
        with self._lock:
            hosted = self.segments.get(table, {})
            segs = [hosted[n] for n in names if n in hosted]
        rt = explain_plan(query, SimpleNamespace(segments=segs),
                          self.executor.pruner,
                          backend=self.executor.backend,
                          use_star_tree=self.executor.use_star_tree)
        return {"columns": rt.schema.column_names,
                "types": rt.schema.column_types, "rows": rt.rows}

    def _handle_query_stream(self, request):
        """Server-streaming query: one DataTable chunk per segment as each
        finishes (reference: GrpcQueryServer.submit streaming per-segment
        blocks for streamable operators, GrpcQueryServer.java:65)."""
        from .datatable import encode

        table = request["table"]
        names = request["segments"]
        query = request["query"]
        with self._lock:
            hosted = self.segments.get(table, {})
            segs = [(n, hosted[n]) for n in names if n in hosted]
            missing = [n for n in names if n not in hosted]

        def stream():
            if missing:
                raise RuntimeError(f"missing routed segments: {missing}")
            with self._tier.reading(table, [n for n, _ in segs]):
                for name, seg in segs:
                    combined, stats = self.executor.execute_segments(
                        query, [seg])
                    stats["segment"] = name
                    yield encode(combined, stats)

        return stream()
