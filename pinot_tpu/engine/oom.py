"""Device-memory (HBM) pressure handling — the DirectOOMHandler analogue.

Reference analogue being replaced:
pinot-core/src/main/java/org/apache/pinot/core/transport/DirectOOMHandler.java
— on a direct-memory OOM the reference tears down Netty channels to shed
load rather than letting the process die. Here the scarce resource is
device HBM: an XLA RESOURCE_EXHAUSTED during plane upload, kernel
dispatch, or result fetch triggers ONE orderly LRU eviction of cold
segment planes from the device cache followed by a single retry; a second
failure fails the QUERY cleanly (surfaced as a broker-style exception,
metered), never the process.

Async-dispatch caveat: XLA dispatch is async, so an OOM raised while the
kernel runs surfaces at the fetch/collect call on error-poisoned output
buffers. Re-fetching those buffers re-raises the stored error no matter
how much memory eviction freed — the retry callable for a fetch seam must
RE-DISPATCH, which is why with_oom_retry takes a separate ``retry_fn``.
"""

from __future__ import annotations

from typing import Callable, Optional

from jax.errors import JaxRuntimeError

from ..spi.metrics import SERVER_METRICS, ServerMeter


class HbmExhaustedError(Exception):
    """Device memory exhausted even after evicting cold segment planes;
    the query fails cleanly (reference: QueryException on OOM-kill)."""


def is_hbm_oom(exc: BaseException) -> bool:
    """XLA surfaces HBM exhaustion as XlaRuntimeError/JaxRuntimeError
    RESOURCE_EXHAUSTED. Message shapes vary by backend/runtime version, so
    within the XLA error type match broadly; for any other RuntimeError
    only the unambiguous RESOURCE_EXHAUSTED tag qualifies (a host-side
    'error allocating thread pool' must not trigger device eviction)."""
    if isinstance(exc, MemoryError):
        return True
    if not isinstance(exc, RuntimeError):
        return False
    msg = str(exc).lower()
    if "resource_exhausted" in msg:
        return True
    if isinstance(exc, JaxRuntimeError):
        return any(m in msg for m in ("out of memory", "failed to allocate",
                                      "allocating", "hbm"))
    return False


def relieve_pressure(keep_segment=None, cache=None) -> int:
    """Evict every cached segment's device planes except the one currently
    executing (its uploads would just be redone), then nudge the runtime to
    actually release the buffers. Stacked [S, N] segment-batch views are
    evicted wholesale first (evict_all_except drops every stack — they are
    derived data, rebuildable from the per-segment planes). Returns bytes
    freed (host-side estimate). ``cache`` defaults to the process-global
    device cache; pass the executor's own cache when it uses a private
    one."""
    import gc

    if cache is None:
        from ..segment.device_cache import GLOBAL_DEVICE_CACHE as cache

    freed, victims = cache.evict_all_except(keep_segment)
    if victims:
        SERVER_METRICS.add_meter(ServerMeter.HBM_OOM_EVICTIONS, victims)
    # realtime device planes are rebuildable from the host segment (the
    # next query re-uploads from row 0) — under OOM they are cold cache
    # like any other plane. keep_segment is a snapshot view; keep its
    # UNDERLYING segment's planes (they back the retry's uploads).
    try:
        from ..realtime.device_plane import REALTIME_PLANES

        keep = getattr(keep_segment, "_seg", keep_segment)
        freed += REALTIME_PLANES.clear(keep=keep)
    except Exception:  # pragma: no cover - relief must never raise
        pass
    gc.collect()  # drop dangling jax.Array refs so XLA can free HBM now
    return freed


def with_oom_retry(fn: Callable, keep_segment=None, cache=None,
                   retry_fn: Optional[Callable] = None,
                   on_relief: Optional[Callable[[int], None]] = None):
    """Run ``fn``; on an HBM OOM, relieve pressure once and retry; on a
    second OOM raise HbmExhaustedError (clean query failure). All other
    exceptions pass through untouched.

    ``retry_fn`` (default ``fn``) is what runs after eviction — pass a
    re-dispatching callable when ``fn`` fetches async outputs, because the
    original output buffers are error-poisoned after an OOM."""
    try:
        return fn()
    except Exception as e:  # noqa: BLE001 — classified below, re-raised if not OOM
        if not is_hbm_oom(e):
            raise
        SERVER_METRICS.add_meter(ServerMeter.HBM_OOM_EVENTS)
        freed = relieve_pressure(keep_segment, cache=cache)
        if on_relief is not None:
            on_relief(freed)
        try:
            return (retry_fn or fn)()
        except Exception as e2:  # noqa: BLE001
            if not is_hbm_oom(e2):
                raise
            SERVER_METRICS.add_meter(ServerMeter.HBM_OOM_QUERY_FAILURES)
            raise HbmExhaustedError(
                f"device memory exhausted after evicting {freed} cached "
                f"bytes and retrying: {e2}") from e2
