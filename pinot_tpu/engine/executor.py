"""Per-segment device executor.

Reference analogue: the server-side operator chain execution under
ServerQueryExecutorV1Impl (pinot-core/.../query/executor/
ServerQueryExecutorV1Impl.java:141) — but one segment = ONE device dispatch
(run_program), not a pull loop of 10K-doc blocks. Host work is limited to:
planning (dictionary lookups), launching the kernel, and decoding occupied
group keys back to values.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from contextlib import contextmanager

import jax
import numpy as np
import jax.numpy as jnp

from ..ops.kernels import (PackedOuts, dict_lookups, fetch_tally,
                           group_table_form, limb_forms, lut_gathers,
                           min_max_forms, pack_outputs, run_program,
                           signed_sums, unpack_outputs)
from .aot_cache import AOT_READY, aot_call
from ..query.context import QueryContext
from ..segment.device_cache import (
    GLOBAL_DEVICE_CACHE,
    DeviceSegmentCache,
    clear_transfer_stats,
    note_dict_pad,
    reset_transfer_stats,
    transfer_stats,
)
from ..segment.loader import ImmutableSegment
from ..spi import faults
from ..spi.trace import (DEVICE_COPY, DEVICE_FETCH, FAMILY_DISPATCH,
                         FETCH_DECODE, GATHER_STACK, TRACING)
from .ir import program_label
from .plan import SegmentPlan, SegmentPlanner
from .results import (
    AggIntermediate,
    GroupArrays,
    GroupByIntermediate,
    SelectionIntermediate,
)
from .selection import selection_from_mask


class _CompileCacheGuard:
    """Process-global valve over jax's UNBOUNDED executable cache.

    A long-lived server compiling unbounded distinct query shapes dies
    with LLVM "Cannot allocate memory" (observed at ~10K distinct shapes
    in a query-fuzz soak). The guard counts distinct (program, padded,
    fused-variant) keys — one per compiled executable family — at the
    same PROCESS scope the jax cache lives at, and drops all jit caches
    wholesale when the limit is hit: recompiling is slow but alive (the
    reference's DirectOOMHandler shed-load philosophy applied to compile
    caches). Bookkeeping is locked; the clear itself is best-effort
    against concurrently-compiling threads."""

    def __init__(self):
        self.limit = int(os.environ.get(
            "PINOT_TPU_COMPILE_CACHE_LIMIT", 4096))
        self._lock = threading.Lock()
        self._seen: set = set()
        self._validated: set = set()  # fused variants proven on-device

    def note(self, key) -> bool:
        """Record a compiled-executable-family key. Returns True when the
        key is NEW (a fresh compile is about to happen) — the per-query
        num_compiles counter feeds off this."""
        with self._lock:
            if key in self._seen:
                return False
            if len(self._seen) >= self.limit:
                logging.getLogger(__name__).warning(
                    "dropping jit caches after %d distinct compiled "
                    "variants (PINOT_TPU_COMPILE_CACHE_LIMIT)",
                    len(self._seen))
                try:
                    jax.clear_caches()
                except Exception:
                    pass  # another thread mid-compile: retry next miss
                else:
                    self._seen.clear()
                    self._validated.clear()
            self._seen.add(key)
            return True

    def validated(self, vkey) -> bool:
        with self._lock:
            return vkey in self._validated

    def mark_validated(self, vkey) -> None:
        with self._lock:
            self._validated.add(vkey)


_GUARD = _CompileCacheGuard()


def _register_compile(gkey, compile_ms: float, program, padded: int,
                      fused: str = "", lut_meta: tuple = (),
                      batch_size: int = 0, mesh: tuple = (),
                      packed: bool = False, aot_example=None) -> None:
    """Cold-path half of the compile telemetry registry: fingerprint the
    freshly-compiled family (a canonical-bytes IR walk — only ever paid
    on a compile-guard miss, next to an actual XLA compile) and record
    the compile cost under it. When the AOT executable cache is enabled
    and the caller provided an (arrays, params, num_docs) example, the
    family is also exported + persisted here — still the cold path, next
    to the XLA compile that just happened. Mesh-sharded executables
    never persist (their validity spans device topology)."""
    from ..cache.keys import family_fingerprint
    from .compile_registry import COMPILE_REGISTRY, describe_family

    fp = family_fingerprint(program, padded, fused, lut_meta, batch_size,
                            mesh=mesh)
    family = describe_family(program, padded, fused, lut_meta, batch_size)
    COMPILE_REGISTRY.note_compile(gkey, compile_ms, fp, family)
    if aot_example is not None and not mesh:
        from . import aot_cache

        if aot_cache.enabled():
            aot_cache.on_compile(
                gkey, fp, compile_ms, family,
                "batch" if batch_size else "solo", program, padded,
                packed=packed, fused=fused, lut_meta=lut_meta,
                example=aot_example)


# (program mode, error type) pairs whose mesh-sharded dispatch already
# failed once — warn once, then fall back quietly to solo batching
_MESH_WARNED: set = set()


def _warn_mesh_fallback(program, err: Exception) -> None:
    # every fallback counts toward the sentinel's fallback-surge window,
    # even when the once-per-key warning below stays quiet
    from .perf_ledger import PERF_LEDGER

    PERF_LEDGER.note_event("mesh-solo")
    key = (getattr(program, "mode", "?"), type(err).__name__)
    if key not in _MESH_WARNED:
        _MESH_WARNED.add(key)
        logging.getLogger(__name__).warning(
            "mesh-sharded dispatch failed (%s: %s); falling back to "
            "single-device batching for %s programs",
            type(err).__name__, err, key[0])

# Per-QUERY dispatch/compile counters. Thread-local because concurrent
# queries share this module: every device dispatch happens on the query's
# own thread (query_executor's host pool never dispatches), so a
# reset-at-start / read-at-end pair on the query thread sees exactly its
# own dispatches — a global snapshot delta would interleave queries.
_TLS = threading.local()


def reset_dispatch_counters() -> None:
    _TLS.counts = [0, 0]  # [num_device_dispatches, num_compiles]


def dispatch_counters() -> tuple[int, int]:
    c = getattr(_TLS, "counts", None)
    return (c[0], c[1]) if c else (0, 0)


def _count_dispatch(new_compile: bool) -> None:
    c = getattr(_TLS, "counts", None)
    if c is not None:
        c[0] += 1
        if new_compile:
            c[1] += 1


def _attach_dispatch_stats(span) -> None:
    """Fold the thread-local transfer counters into a finished
    family-dispatch span (traced paths only)."""
    stats = transfer_stats()
    if stats is not None:
        span.set_attribute("transferBytes", stats["transferBytes"])
        if stats["transfers"]:
            span.set_attribute("transfers", dict(stats["transfers"]))
        span.set_attribute("stackHits", stats["stackHits"])
        span.set_attribute("stackMisses", stats["stackMisses"])
        span.set_attribute("dictPads", stats["dictPads"])
    clear_transfer_stats()


@contextmanager
def device_fetch():
    """The DEVICE_FETCH span around a blocking device→host fetch: the one
    place a request waits for the device, traced or not. Its duration is
    the wait — queueing behind other requests' programs, execution, the
    output pack — the copy and the decode. The fetch sites split it into
    two children: DEVICE_COPY, the wait for the results and the copy (the
    call that materializes them: the program cannot tell the two apart
    without a sync of its own, the device trace can), and FETCH_DECODE,
    the host's work on the fetched bytes. `hostFetches` and `fetchBytes`
    count what crossed inside it (this thread's own,
    kernels.fetch_tally)."""
    with TRACING.scope(DEVICE_FETCH) as span:
        if span is None:
            yield
            return
        with fetch_tally() as tally:
            try:
                yield
            finally:
                span.set_attribute("hostFetches", tally[0])
                span.set_attribute("fetchBytes", tally[1])


def fetch_outputs(outs) -> list:
    """A dispatch's outputs as host arrays. Arrays that are on the host
    already (a batch family's fetched rows) pass through; anything else
    blocks under DEVICE_FETCH."""
    if isinstance(outs, PackedOuts):
        with device_fetch():
            return unpack_outputs(outs)
    if all(isinstance(o, np.ndarray) for o in outs):
        return list(outs)
    with device_fetch():
        with TRACING.scope(DEVICE_COPY):
            host = [np.asarray(o) for o in outs]
        # each output crossed as its own array: nothing to decode, and
        # the span says so, as every fetch has both children
        with TRACING.scope(FETCH_DECODE):
            return host


class BatchFamilyMismatch(Exception):
    """A family grouped by the host-side key turned out to gather planes of
    unequal dtype/shape — the caller falls back to per-segment dispatch."""


def _dict_pad(card: int) -> int:
    """Shape bucket for dictionary-values planes: next power of two ≥ card.
    Dict planes are only ever gathered by ids < the segment's OWN
    cardinality, so zero-padding to a shared bucket lets segments with
    different dictionary sizes join one batch family without changing any
    gathered value."""
    b = 1
    while b < card:
        b <<= 1
    return b


def _build_stack(col: tuple, shape: tuple, pad: int, ndev: int):
    """One slot's [S, ...] stack, built on a stacked-view miss only: each
    member zero-padded to `shape` (a dictionary plane's family bucket),
    the last member repeated `pad` times (a ragged mesh family), the stack
    sharded over the segment axis when ndev > 1."""
    members = []
    for a in col:
        if a.shape != shape:
            a = jnp.pad(a, [(0, t - n) for t, n in zip(shape, a.shape)])
            note_dict_pad()
        members.append(a)
    stack = jnp.stack(members + [members[-1]] * pad)
    if ndev > 1:
        from ..parallel import mesh as pmesh

        stack = jax.device_put(stack,
                               pmesh.segment_sharding(ndev, stack.ndim))
    return stack


def batch_family_key(segment: ImmutableSegment, plan: SegmentPlan,
                     mesh: tuple = ()):
    """Host-computable batch family key: segments with equal keys gather
    identically-shaped device planes and params, so their kernel inputs can
    stack into [S, ...] arrays and run as ONE vmapped dispatch.

    The key is (program, padded bucket, per-slot dtype/packing signature,
    per-param dtype/shape signature) — derived purely from column METADATA
    (no device upload), so EXPLAIN and the dispatcher share it. When mesh
    execution is active the mesh shape joins the key so sharded and solo
    executables cache separately (compile_registry.family_fingerprint gains
    the same axis). It mirrors what gather_arrays_packed will produce;
    dispatch_plan_batch re-verifies the real gathered shapes and raises
    BatchFamilyMismatch if the mirror ever drifts. Returns None when a
    slot's shape can't be predicted."""
    from ..segment.device_cache import pad_bucket, packed_hbm_enabled
    from ..spi.data_types import DataType

    padded = pad_bucket(max(1, segment.num_docs))
    packed_on = packed_hbm_enabled()
    sig = []
    try:
        for column, kind in plan.slots:
            m = segment.column_metadata(column)
            if kind == "ids" and not m.single_value:
                kind = "mvids"  # view.dict_ids falls through to the matrix
            if kind == "ids":
                bits = getattr(m, "bits_per_value", 32) or 32
                width = 32
                if bits <= 16 and packed_on:
                    width = 8 if bits <= 8 else 16
                sig.append(("ids", width))
            elif kind == "mvids":
                sig.append(("mvids", max(1, m.max_number_of_multi_values)))
            elif kind == "raw":
                sig.append(("raw", str(DataType(m.data_type).numpy_dtype)))
            elif kind == "rawf32r":
                sig.append(("rawf32r",))
            elif kind == "dict":
                sig.append(("dict", str(DataType(m.data_type).numpy_dtype),
                            _dict_pad(int(m.cardinality))))
            elif kind == "null":
                sig.append(("null",))
            else:
                return None
        psig = tuple((str(np.asarray(p).dtype), np.asarray(p).shape)
                     for p in plan.params)
    except Exception:
        return None
    key = (plan.program, padded, tuple(sig), psig)
    if mesh:
        key = key + (("mesh",) + tuple(mesh),)
    return key


def batch_families(pairs: list, mesh: tuple = (), batch: bool = True):
    """(plans, [(fkey, positions)]) of one query's (segment, plan) pairs:
    the plans with their sorted tables sized alike (plan.share_table_size:
    segments of one table then share a Program), grouped into batch
    families by `batch_family_key`, in first-seen order; fkey is None for
    a pair that cannot batch (unpredictable slot shapes, or `batch` off).
    The one place where a query's plans become families: the dispatcher,
    the device combine and EXPLAIN all come through here."""
    from .plan import share_table_size

    plans = share_table_size([plan for _, plan in pairs])
    if len(pairs) < 2 or not batch:
        return plans, [(None, [i]) for i in range(len(pairs))]
    groups: dict = {}
    for pos, ((segment, _), plan) in enumerate(zip(pairs, plans)):
        fkey = batch_family_key(segment, plan, mesh)
        groups.setdefault(("__solo__", pos) if fkey is None else fkey,
                          []).append(pos)
    return plans, [(None if k[0] == "__solo__" else k, positions)
                   for k, positions in groups.items()]


class TpuSegmentExecutor:
    """Executes one QueryContext against one segment on the device."""

    def __init__(self, cache: DeviceSegmentCache = None):
        self.cache = cache or GLOBAL_DEVICE_CACHE

    def plan(self, query: QueryContext, segment: ImmutableSegment) -> SegmentPlan:
        if getattr(segment, "is_mutable", False):
            # consuming-segment snapshots lower through the realtime
            # planner (value-space ranges, no MV/rebased planes); its
            # UnsupportedQueryError falls back to host like any other
            from ..realtime.device_plane import realtime_plan

            return realtime_plan(query, segment)
        return SegmentPlanner(query, segment).plan()

    def _view_for(self, segment):
        """Device view: the HBM cache for immutable segments, the
        realtime plane registry (delta-uploaded append-only planes) for
        consuming-segment snapshots."""
        if getattr(segment, "is_mutable", False):
            from ..realtime.device_plane import REALTIME_PLANES

            return REALTIME_PLANES.view(segment)
        return self.cache.view(segment)

    def dispatch_plan(self, segment: ImmutableSegment, plan: SegmentPlan):
        """Launch the kernel and return UN-materialized device outputs.

        JAX dispatch is asynchronous: the caller can dispatch every
        segment's kernel back-to-back so the device queue stays full, then
        collect() each — host planning/decoding overlaps device compute
        (replaces the reference's per-segment worker-pool combine,
        pinot-core/.../operator/combine/GroupByCombineOperator.java:54, with
        async device queueing instead of threads)."""
        return self._traced_dispatch(
            [segment], lambda span: self._dispatch_plan(segment, plan, span))

    def dispatch_plan_raw(self, segment: ImmutableSegment, plan: SegmentPlan):
        """dispatch_plan without the flat-buffer packing: returns (the raw
        device output tuple, the segment's view) for callers that keep
        computing ON DEVICE with the per-segment outputs (the sparse device
        combine, query_executor._sparse_device_combine) rather than
        fetching them. Sparse programs never take the fused path, so the
        fused negotiation is skipped."""
        return self._traced_dispatch(
            [segment],
            lambda span: self._dispatch_plan(segment, plan, span, raw=True))

    def _traced_dispatch(self, segments: list, body, batch: bool = False):
        """The one wrapper around every dispatch body: the fault point,
        and, when a trace is active, the family_dispatch span: gather +
        enqueue, with the compile time (detected via the compile-cache
        guard, measured without a sync), the program's label, per-slot
        transfer bytes. The span adds no sync: the wait
        for the device is the DEVICE_FETCH span, where the untraced path
        waits too. Tracing off takes the first branch: one thread-local
        read, no spans."""
        if faults.ACTIVE:
            # kind="hbm_oom" specs raise RESOURCE_EXHAUSTED here and are
            # absorbed by the caller's with_oom_retry — the real OOM path
            ctx = {"batch_size": len(segments)} if batch else {}
            faults.FAULTS.fire("device.dispatch", segment=segments[0].name,
                               **ctx)
        if TRACING.active_trace() is None:
            return body(None)
        with TRACING.scope(FAMILY_DISPATCH) as span:
            reset_transfer_stats()
            try:
                if not batch:
                    span.set_attribute("segment", segments[0].name)
                span.set_attribute("numSegments", len(segments))
                return body(span)
            finally:
                _attach_dispatch_stats(span)

    @staticmethod
    def _launch(span, gkey, program, padded: int, arrays, params, run,
                count_after: bool = False, **family):
        """The one launch routine: note the compile key with the guard,
        count the dispatch, say in the span what runs, run, and register
        the compile (``family``: what `_register_compile` files it under)
        or the dispatch. jit's first call compiles synchronously before the
        async dispatch, so the host wall of ``run`` ≈ the compile cost on a
        guard miss — measurable WITHOUT a sync, so the compile registry
        gets fed on untraced production dispatches too. ``count_after``
        counts only once ``run`` returned (a sharded dispatch that fails
        falls back to a body that counts itself)."""
        new_compile = _GUARD.note(gkey)
        if not count_after:
            _count_dispatch(new_compile)
        if span is not None:
            # mode, label, row bucket, how the planes the program is fed
            # decode their dictionaries (kernels.dict_lookups), how many
            # of its LUT filters gather (kernels.lut_gathers; the fused
            # kernel takes a LUT as id runs, no gather), how a
            # dense table takes its MINs and MAXs (kernels.min_max_forms),
            # the slots of a member's group table (0: it has none), which
            # table that is (kernels.group_table_form), the int8 planes
            # its limb pass carries and the SUMs left to a scatter
            # (kernels.limb_forms), the SUMs of a value that may be
            # negative (kernels.signed_sums)
            span.set_attribute("mode", program.mode)
            span.set_attribute("program", program_label(program))
            span.set_attribute("padded", padded)
            span.set_attribute("dictLookups", dict_lookups(program, arrays))
            span.set_attribute("lutGathers", 0 if family.get("fused")
                               else lut_gathers(program, params))
            span.set_attribute("minMax", min_max_forms(program))
            span.set_attribute(
                "groupSlots", program.num_groups
                if program.mode in ("group_by", "group_by_sparse") else 0)
            span.set_attribute("groupTable", group_table_form(program))
            planes, scatters = limb_forms(program, padded, arrays)
            span.set_attribute("limbPlanes", planes)
            span.set_attribute("sumScatters", scatters)
            span.set_attribute("signedSums", signed_sums(program))
        t0 = time.perf_counter()
        outs = run()
        if count_after:
            _count_dispatch(new_compile)
        compile_ms = 0.0
        if new_compile:
            compile_ms = round((time.perf_counter() - t0) * 1000, 3)
            _register_compile(gkey, compile_ms, program, padded, **family)
        else:
            # the warm path: one dict lookup + counter bumps, no
            # fingerprint walk (tests/test_tracing_perf_guard.py)
            from .compile_registry import COMPILE_REGISTRY

            COMPILE_REGISTRY.note_dispatch(gkey)
        if span is not None:
            span.set_attribute("compileMs", compile_ms)
        return outs

    def _dispatch_plan(self, segment: ImmutableSegment, plan: SegmentPlan,
                       span, raw: bool = False):
        """The solo body. ``raw``: the fused kernel is off and nothing is
        packed — (outs, view) for a caller that stays on the device."""
        view = self._view_for(segment)
        arrays, packed = plan.gather_arrays_packed(view)
        # params pass as host numpy: jit converts arguments itself — an
        # eager jnp.asarray per param costs a device dispatch each (~1ms ×
        # params × segments of pure host overhead per multi-segment query).
        # Python ints still pin to int64 (the dtype the old jnp.asarray
        # produced under x64).
        params = tuple(p if isinstance(p, (np.ndarray, np.generic))
                       else np.asarray(p) for p in plan.params)
        from ..ops import fused_groupby

        # decide HERE whether the fused kernel applies, so the failure
        # fallback below can never be tripped (and permanently disable
        # fusion) by an error from a program the fused path never touched.
        # Dict-LUT predicates (IN/LIKE/NOT...) join the fused scope when
        # their boolean LUT compresses to a few contiguous dict-id runs —
        # a dispatch-time property of the CONCRETE host params.
        fused = fused_groupby.active() if plan.fused_ok and not raw else ""
        lut_meta: tuple = ()
        base_params = params
        if fused:
            extra, lut_meta = fused_groupby.lut_run_params(
                plan.program, params)
            if plan.program.mode == "group_by" and fused_groupby.plan(
                    plan.program, arrays, lut_meta) is not None:
                params = params + extra  # run arrays ride as extra params
            else:
                fused, lut_meta = "", ()
        # one entry per compiled executable family: padded shape and the
        # fused/lut variants each compile separately
        gkey = (plan.program, view.padded, fused, lut_meta)
        if span is not None and fused:
            span.set_attribute("fused", fused)
        nd = np.int32(segment.num_docs)

        def run():
            # AOT-prewarmed family (engine/aot_cache.py): the persisted
            # executable serves the dispatch — zero compiles in this
            # process for the family. Empty/disabled cache costs one
            # falsy truth test. A failed AOT call returns None and the
            # jit path below runs (its compile then goes uncounted —
            # the guard was seeded at prewarm — a deliberate trade in a
            # corruption-recovery path that should never recur).
            outs = aot_call(gkey, arrays, params, nd) if AOT_READY else None
            if outs is None:
                outs = run_program(plan.program, arrays, params, nd,
                                   view.padded, packed=packed, fused=fused,
                                   fused_lut_meta=lut_meta)
            return outs

        try:
            outs = self._launch(span, gkey, plan.program, view.padded,
                                arrays, params, run, fused=fused,
                                lut_meta=lut_meta,
                                packed=packed,
                                aot_example=(arrays, params, nd))
            # the compiled fused kernel varies with lut_meta (run counts
            # are static), so validation is keyed per (program, meta)
            vkey = (plan.program, lut_meta)
            if fused and not _GUARD.validated(vkey):
                # dispatch is async: a device-side kernel failure would
                # otherwise surface at collect(), past this fallback. Block
                # ONCE per compiled variant to prove the kernel end-to-end;
                # later executions stay fully async.
                jax.block_until_ready(outs)
                _GUARD.mark_validated(vkey)
        except Exception as e:
            if not fused:
                raise
            # Mosaic/VMEM failure on this machine's toolchain: disable the
            # fused kernel for the process and recompile the two-step
            # path — with the ORIGINAL params so this compile is the one
            # every later (post-disable) dispatch of the program reuses
            fused_groupby.note_failure(e)
            from .perf_ledger import PERF_LEDGER

            PERF_LEDGER.note_event("fused-host")
            outs = run_program(plan.program, arrays, base_params, nd,
                               view.padded, packed=packed, fused="")
            if span is not None:
                span.set_attribute("fusedFallback", True)
                span.attributes.setdefault("compileMs", 0.0)
        if raw:
            return outs, view
        # one flat buffer per query → one D2H transfer at collect()
        return pack_outputs(outs, program_label(plan.program))

    def _gather_batch(self, segments: list, plans: list, ndev: int = 1):
        with TRACING.scope(GATHER_STACK):
            return self._gather_batch_inner(segments, plans, ndev)

    def _gather_batch_inner(self, segments: list, plans: list, ndev: int):
        """Gather + stack a batch family's kernel inputs: per-member planes
        come from the per-segment HBM cache (gather_arrays_packed — upload
        happens at most once per plane), the [S, ...] stacks from the
        cache's stacked-view layer (derived copies under the same byte
        budget). With ndev > 1 the stacks are built SHARDED across the
        segment mesh axis (NamedSharding over the leading dim) and ragged
        families pad to a multiple of ndev by repeating the last member
        with num_docs=0 — the kernel's row-validity mask makes pad slots
        contribute nothing. Raises BatchFamilyMismatch if the members'
        gathered planes disagree in dtype/shape/packing — the host-side
        family key should prevent that; the check makes a drift fall back,
        not corrupt."""
        views = [self._view_for(s) for s in segments]
        gathered = [pl.gather_arrays_packed(v)
                    for pl, v in zip(plans, views)]
        packed = gathered[0][1]
        nslots = len(gathered[0][0])
        for arrs, pk in gathered[1:]:
            if pk != packed or len(arrs) != nslots:
                raise BatchFamilyMismatch("packing/slot-count mismatch")
        pad = 0
        if ndev > 1:
            pad = (-len(segments)) % ndev
        sview = self.cache.stacked_view(segments)
        stacked = []
        for i in range(nslots):
            col = [g[0][i] for g in gathered]
            shapes = [a.shape for a in col]
            if plans[0].slots[i][1] == "dict":
                # dictionary sizes are segment-local: every member's values
                # plane is zero-padded to the family's shared power-of-two
                # bucket (see _dict_pad — pads are never gathered). The
                # shapes come from metadata; the pads run in _build_stack,
                # only when the stack is not cached
                target = _dict_pad(max(s[0] for s in shapes))
                shapes = [(target,) + s[1:] for s in shapes]
            s0, d0 = shapes[0], col[0].dtype
            if any(s != s0 or a.dtype != d0
                   for s, a in zip(shapes[1:], col[1:])):
                raise BatchFamilyMismatch(
                    f"slot {i} ({plans[0].slots[i]}): unequal plane "
                    f"shapes/dtypes across family members")
            pkey = (plans[0].slots[i], str(d0), tuple(s0))
            if ndev > 1:
                pkey = pkey + (("mesh", ndev),)
            stacked.append(sview.plane(pkey, lambda c=tuple(col), s=s0:
                                       _build_stack(c, s, pad, ndev)))
        nparams = len(plans[0].params)
        if any(len(pl.params) != nparams for pl in plans):
            raise BatchFamilyMismatch("param-count mismatch")
        params_b = []
        for j in range(nparams):
            ps = [np.asarray(pl.params[j]) for pl in plans]
            p0 = ps[0]
            if any(p.shape != p0.shape or p.dtype != p0.dtype
                   for p in ps[1:]):
                raise BatchFamilyMismatch(f"param {j}: shape/dtype mismatch")
            if pad:
                ps = ps + [ps[-1]] * pad
            params_b.append(np.stack(ps))
        num_docs = np.asarray([s.num_docs for s in segments] + [0] * pad,
                              dtype=np.int32)
        return views, tuple(stacked), tuple(params_b), packed, num_docs

    def _dispatch_batch_sharded(self, segments: list, plans: list, span,
                                ndev: int, pack: bool):
        """ONE sharded dispatch for the whole family: the [S, ...] stacks
        split across mesh[SEGMENT_AXIS] so every local chip runs S/ndev
        members concurrently, then results merge ON DEVICE (pack → flat on
        device 0, or raw gather over ICI) before the query's single host
        crossing. Per-row math is the solo vmap body — bit-identical."""
        from ..parallel import mesh as pmesh

        views, arrays, params_b, packed, num_docs = self._gather_batch(
            segments, plans, ndev=ndev)
        plan0 = plans[0]
        asig = tuple((str(a.dtype), tuple(a.shape)) for a in arrays)
        gkey = ("batchmesh", ndev, plan0.program, views[0].padded, packed,
                asig, len(segments))
        if span is not None:
            span.set_attribute("meshDevices", ndev)
        # counted only after the sharded dispatch succeeded: a trace-time
        # failure falls back to the solo path, which counts itself — so
        # numDeviceDispatches stays exactly one per family either way
        outs = self._launch(
            span, gkey, plan0.program, views[0].padded, arrays, params_b,
            lambda: pmesh.run_program_batch_sharded(
                plan0.program, arrays, params_b, num_docs, views[0].padded,
                ndev, packed=packed),
            count_after=True, batch_size=len(segments), mesh=(ndev,))
        if span is not None:
            # the record of which chips took part; when each ran is in the
            # profiler's per-device planes, not in a host stamp
            for d in pmesh.mesh_devices(ndev):
                with TRACING.scope(f"mesh_device:{d.id}") as dspan:
                    dspan.set_attribute("device", d.id)
        if pack:
            label = program_label(plan0.program)
            try:
                # preferred: shuffle-inside-the-program — all_gather over
                # the mesh axis + on-device pack, no dev0 funnel of raw outs
                result = pmesh.pack_outputs_collective(
                    outs, len(segments), ndev, label)
            except Exception as e:
                from .oom import HbmExhaustedError

                if isinstance(e, HbmExhaustedError):
                    raise
                result = pmesh.pack_outputs_gathered(outs, len(segments),
                                                     label)
        else:
            result = pmesh.gather_outputs(outs, len(segments))
        return result, views

    def _dispatch_batch(self, segments: list, plans: list, mesh: tuple,
                        pack: bool):
        return self._traced_dispatch(
            segments, lambda span: self._dispatch_batch_inner(
                segments, plans, span, mesh, pack), batch=True)

    def _dispatch_batch_inner(self, segments: list, plans: list, span,
                              mesh: tuple, pack: bool):
        from ..ops.kernels import run_program_batch

        ndev = int(mesh[0]) if mesh else 1
        if ndev > 1 and len(segments) >= ndev:
            try:
                return self._dispatch_batch_sharded(segments, plans, span,
                                                    ndev, pack)
            except BatchFamilyMismatch:
                raise
            except Exception as e:
                from .oom import HbmExhaustedError

                if isinstance(e, HbmExhaustedError):
                    raise
                _warn_mesh_fallback(plans[0].program, e)
        views, arrays, params_b, packed, num_docs = self._gather_batch(
            segments, plans)
        plan0 = plans[0]
        # batch compiles are keyed per FAMILY (program, bucket, slot sig,
        # batch size) — the executable cache scales with families, not S
        asig = tuple((str(a.dtype), tuple(a.shape)) for a in arrays)
        gkey = ("batch", plan0.program, views[0].padded, packed, asig,
                len(segments))

        def run():
            outs = aot_call(gkey, arrays, params_b, num_docs) \
                if AOT_READY else None
            if outs is None:
                outs = run_program_batch(plan0.program, arrays, params_b,
                                         num_docs, views[0].padded,
                                         packed=packed)
            return outs

        outs = self._launch(span, gkey, plan0.program, views[0].padded,
                            arrays, params_b, run, batch_size=len(segments),
                            packed=packed,
                            aot_example=(arrays, params_b, num_docs))
        return outs, views

    def dispatch_plan_batch(self, segments: list, plans: list,
                            mesh: tuple = ()):
        """ONE vmapped device dispatch for a whole batch family (equal
        batch_family_key). Returns a PackedOuts whose arrays carry a
        leading [S] dim; the caller slices row s for member s and feeds the
        slices through collect() unchanged — bit-for-bit what S separate
        dispatch_plan(..., fused='') calls would return, for one launch and
        one D2H transfer. With `mesh=(ndev,)` and S ≥ ndev the stack shards
        across the local device mesh and the byte-pack happens on device
        with the flat committed to device 0 — still one launch, one D2H.
        Raises BatchFamilyMismatch to request the per-segment fallback."""
        outs, _ = self._dispatch_batch(segments, plans, mesh, True)
        return outs if isinstance(outs, PackedOuts) \
            else pack_outputs(outs, program_label(plans[0].program))

    def dispatch_plan_batch_raw(self, segments: list, plans: list,
                                mesh: tuple = ()):
        """dispatch_plan_batch without the flat-buffer packing: returns
        (outs, views) with every output carrying a leading [S] dim, for
        callers that keep computing on device (the batched sparse device
        combine slices per-member rows lazily — the slices never leave
        HBM). Mesh-sharded dispatches gather their outputs to device 0
        over ICI first so downstream device math colocates."""
        return self._dispatch_batch(segments, plans, mesh, False)

    def collect(self, query: QueryContext, segment: ImmutableSegment,
                plan: SegmentPlan, outs):
        """Materialize device outputs (blocks) and decode the intermediate."""
        outs = fetch_outputs(outs)
        mode = plan.program.mode
        if mode == "selection":
            return self._selection_result(query, segment, plan, outs[0])
        if mode == "aggregation":
            states = [la.extract(outs, 0) for la in plan.lowered_aggs]
            return AggIntermediate(states, num_docs_scanned=int(outs[0][0]))
        return self._group_by_result(plan, outs)

    def _group_by_result(self, plan: SegmentPlan, outs) -> GroupByIntermediate:
        num_groups = plan.program.num_groups
        mv_docs = None
        if plan.program.mv_group_slot is not None:
            # MV expansion: pair counts ≠ docs; the kernel appends the
            # matched DOC count as one extra trailing output
            mv_docs = int(outs[-1][0])
            outs = outs[:-1]
        counts = outs[0][:num_groups]
        gids = np.nonzero(counts)[0]
        if plan.program.mode == "group_by_sparse":
            # sparse kernels emit the surviving composite keys as the last
            # output; gids are table slots, keys carry the dict-id composite
            composite = outs[-1][gids].astype(np.int64)
        else:
            composite = gids
        # decompose composite key → per-dim dict ids → values
        # (inverse of DictionaryBasedGroupKeyGenerator's cartesian key,
        # pinot-core/.../groupby/DictionaryBasedGroupKeyGenerator.java:119-137)
        key_cols = []
        for dim, stride in zip(plan.group_dims, plan.program.group_strides):
            ids = (composite // stride) % dim.cardinality
            key_cols.append(dim.dictionary.values[ids])
        scanned = int(counts.sum())
        trimmed = False
        if plan.program.mode == "group_by_sparse":
            # sparse trash slot = valid rows whose group was trimmed; they
            # were still scanned (reference reports all post-filter docs)
            trash = int(outs[0][num_groups])
            scanned += trash
            # an ORDER-BY-pushdown trim is exact — not a groups-limit event
            trimmed = trash > 0 and not plan.program.exact_trim
        if mv_docs is not None:
            scanned = mv_docs  # docs matched, not (doc × entry) pairs
        if all(la.vec is not None for la in plan.lowered_aggs):
            # columnar fast path: states stay numpy end-to-end (dict form
            # costs ~µs/group in Python — fatal at numGroupsLimit scale)
            return GroupArrays(
                [np.asarray(col) for col in key_cols],
                [la.vec.extract(outs, gids) for la in plan.lowered_aggs],
                [la.vec.spec for la in plan.lowered_aggs],
                [la.vec.fin_tag for la in plan.lowered_aggs],
                num_docs_scanned=scanned, groups_trimmed=trimmed)
        # per-agg batch extractors: prepare() runs once per output (e.g.
        # decoding the sparse distinct pair list in one vectorized pass)
        extractors = [
            la.prepare(outs) if la.prepare is not None
            else (lambda g, _la=la: _la.extract(outs, g))
            for la in plan.lowered_aggs]
        groups = {}
        for row, g in enumerate(gids):
            key = tuple(_to_python(col[row]) for col in key_cols)
            groups[key] = [ex(g) for ex in extractors]
        return GroupByIntermediate(groups, num_docs_scanned=scanned,
                                   groups_trimmed=trimmed)

    def _selection_result(self, query, segment, plan, mask) -> SelectionIntermediate:
        evaluator = None
        if plan.selection_exprs:
            from .host_executor import HostSegmentExecutor

            host = HostSegmentExecutor()
            evaluator = lambda e, doc_ids: host.eval_value_at(e, segment, doc_ids)  # noqa: E731
        # kernel emits the mask bit-packed (kernels.py selection mode);
        # decode through the repo's one little-endian bitmap helper
        from ..segment.bitpack import unpack_bitmap

        bits = unpack_bitmap(np.asarray(mask), segment.num_docs)
        return selection_from_mask(query, segment, plan.selection_columns,
                                   bits,
                                   extra_exprs=plan.selection_exprs or None,
                                   evaluator=evaluator)


def _to_python(v):
    if isinstance(v, np.generic):
        return v.item()
    return v
