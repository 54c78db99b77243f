"""Single-pass fused dense group-by: filter + gid + limbs INSIDE the MXU
kernel.

Why: a Pallas call is opaque to XLA — nothing fuses INTO it. The two-step
dense path (ops/kernels.py `_dense_group_by_entry` → mxu_groupby.limb_sums)
therefore materializes every intermediate to HBM: the widened id planes,
the filter mask, the int32 gid vector, and one int8 limb plane per 7 bits
of every summed column. For SSB q2 at 100M rows that turns an 800MB
problem into ~2.8GB of HBM traffic. This kernel reads each RAW column
plane (uint8/uint16/int32, exactly as resident in HBM) once per block,
computes mask → gid → limb planes in VMEM, and feeds them straight into
the same Kronecker-factored one-hot matmul chain (mxu_groupby._matmul_tail)
— no intermediate ever touches HBM.

Scope (the common hot shape; everything else stays on the two-step path):
  * filter: None / TRUE / a CONJUNCTION of closed dict-id or raw-int32
    intervals (EQ, BETWEEN, range — what sorted dictionaries normalize
    predicates to at plan time; reference: the predicate→dict-id-interval
    rewrite replacing PredicateEvaluator trees)
  * group key: plain id-plane slots with static strides
  * aggregations: COUNT and int32-exact SUMs (the MXU limb recipe)

Runtime bounds ride a scalar-prefetch vector (SMEM), so one compiled
kernel serves every literal value of the same query shape. Failures
(unsupported dtype on a given Mosaic version, VMEM pressure) permanently
fall back to the two-step path via note_failure() — the dispatcher retries
the same program unfused.

Reference analogue being replaced: the per-block filter→transform→
aggregate operator chain (pinot-core/.../query/aggregation/groupby/
DefaultGroupByExecutor.java:191) — collapsed into one systolic-array pass.
"""

from __future__ import annotations

import functools
import logging
import os
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import mxu_groupby
from ..engine import ir

logger = logging.getLogger(__name__)

_I32_MIN = -(1 << 31)
_I32_MAX = (1 << 31) - 1
_MAX_TERMS = 8

_STATE: dict = {"error": None}


def active() -> str:
    """'' = off | 'tpu' = real kernel | 'interpret' = interpret mode (CPU
    tests). Controlled by PINOT_TPU_FUSED: auto (default, on when the TPU
    backend is live) | 1 | 0 | interpret."""
    if _STATE["error"] is not None:
        return ""
    mode = os.environ.get("PINOT_TPU_FUSED", "auto")
    if mode == "0":
        return ""
    if mode == "interpret":
        return "interpret"
    if mode in ("auto", "1"):
        return "tpu" if mxu_groupby.backend_platform() == "tpu" else ""
    return ""


def note_failure(e: BaseException) -> None:
    if _STATE["error"] is None:
        logger.warning("fused group-by disabled after failure: %s", e)
        _STATE["error"] = e


@dataclass(frozen=True)
class FusedPlan:
    # ("iv", slot, lo_param|None, hi_param|None, lo_inc, hi_inc)
    # | ("runs", slot, runs_param, n_runs) — a dict-LUT predicate (IN,
    #   LIKE, NOT...) whose boolean LUT compresses to n_runs contiguous
    #   dict-id ranges; the [lo0,hi0,lo1,hi1,...] i64 array rides in
    #   params[runs_param] (appended at dispatch — lut_run_params)
    terms: tuple
    groups: tuple  # (slot, stride)
    # ("count",) | ("limb", slot, shift) | ("neg", slot)
    planes: tuple
    # per agg: ("count",) | ("sum", ((plane_idx, shift), ...), neg_idx|None)
    recipes: tuple
    slots: tuple  # unique slots the kernel loads, in ref order


MAX_LUT_RUNS = 4


def lut_run_params(program: ir.Program, params):
    """Dispatch-time (host, CONCRETE params) analysis: for each Lut filter
    leaf whose boolean LUT is a union of ≤ MAX_LUT_RUNS contiguous
    dict-id ranges, build the [lo,hi,...] run array. Returns
    (extra_params, meta) — meta is the STATIC ((lut_param, appended_param
    index, n_runs), ...) that keys the jit trace; ((), ()) when any Lut
    doesn't compress (the program then stays on the two-step path)."""
    if program.mode != "group_by" or program.filter is None:
        return (), ()
    extra: list = []
    meta: list = []
    base = len(params)
    for leaf in _filter_leaves(program.filter):
        if not isinstance(leaf, ir.Lut):
            continue
        lut = np.asarray(params[leaf.lut_param])
        if lut.dtype != np.bool_ or lut.ndim != 1:
            return (), ()
        idx = np.flatnonzero(lut)
        if idx.size == 0:
            runs = np.asarray([1, 0], dtype=np.int64)  # empty interval
        else:
            breaks = np.flatnonzero(np.diff(idx) > 1)
            starts = np.concatenate([[idx[0]], idx[breaks + 1]])
            ends = np.concatenate([idx[breaks], [idx[-1]]])
            if len(starts) > MAX_LUT_RUNS:
                return (), ()
            runs = np.empty(2 * len(starts), dtype=np.int64)
            runs[0::2] = starts
            runs[1::2] = ends
        meta.append((leaf.lut_param, base + len(extra), len(runs) // 2))
        extra.append(runs)
    return tuple(extra), tuple(meta)


def _filter_leaves(node):
    if isinstance(node, ir.FAnd):
        for c in node.children:
            yield from _filter_leaves(c)
    else:
        yield node


def plan(program: ir.Program, arrays,
         lut_meta: tuple = ()) -> Optional[FusedPlan]:
    """Static shape analysis; `arrays` contributes only dtypes/ndims (known
    at trace time). Returns None when the program leaves the fused scope.
    ``arrays=None`` checks program STRUCTURE only (EXPLAIN eligibility:
    Lut leaves count as eligible — run-compression is a dispatch-time
    property). ``lut_meta`` is lut_run_params' static description of the
    appended run arrays."""
    if program.mode != "group_by" or program.mv_group_slot is not None:
        return None
    if program.group_vexprs or not program.group_slots:
        return None

    def plane_ok(slot, payload=False):
        if arrays is None:
            return True
        a = arrays[slot]
        if getattr(a, "ndim", None) != 1:
            return False
        dt = a.dtype
        if payload:
            return dt == jnp.int32
        return dt in (jnp.uint8, jnp.uint16, jnp.int32)

    runs_of = {m[0]: m for m in lut_meta}
    terms = []
    if program.filter is not None:
        for leaf in _filter_leaves(program.filter):
            if isinstance(leaf, ir.FConst):
                if leaf.value:
                    continue
                return None
            if isinstance(leaf, ir.Lut):
                if leaf.mv or not plane_ok(leaf.ids_slot):
                    return None
                m = runs_of.get(leaf.lut_param)
                if m is None:
                    if arrays is None:  # EXPLAIN structural eligibility
                        terms.append(("runs", leaf.ids_slot, -1, 1))
                        continue
                    return None
                terms.append(("runs", leaf.ids_slot, m[1], m[2]))
                continue
            if not isinstance(leaf, ir.Interval):
                return None
            ve = leaf.vexpr
            if not isinstance(ve, (ir.IdsCol, ir.Col)) or \
                    not plane_ok(ve.slot):
                return None
            terms.append(("iv", ve.slot, leaf.lo_param, leaf.hi_param,
                          leaf.lo_inclusive, leaf.hi_inclusive))
    if len(terms) > _MAX_TERMS:
        return None

    for slot in program.group_slots:
        if not plane_ok(slot):
            return None
    groups = tuple(zip(program.group_slots, program.group_strides))

    # limb policy comes from the ONE shared helper so fused and two-step
    # sums can never drift (kernels._limb_shifts)
    from .kernels import _limb_shifts

    planes: list = [("count",)]
    recipes: list = []
    b = mxu_groupby.LIMB_BITS
    for agg in program.aggs:
        if agg.kind == "count":
            recipes.append(("count",))
            continue
        if agg.kind != "sum" or not isinstance(agg.vexpr, ir.Col) or \
                not plane_ok(agg.vexpr.slot, payload=True):
            return None
        slot = agg.vexpr.slot
        shifts, nonneg = _limb_shifts(agg.vmin, agg.vmax, b)
        refs = tuple((len(planes) + k, s) for k, s in enumerate(shifts))
        planes.extend(("limb", slot, s) for s in shifts)
        neg_idx = None
        if not nonneg:
            neg_idx = len(planes)
            planes.append(("neg", slot))
        recipes.append(("sum", refs, neg_idx))

    num_segments = program.num_groups + 1
    if not mxu_groupby.supports(num_segments, len(planes)):
        return None

    slots = []
    for term in terms:
        if term[1] not in slots:
            slots.append(term[1])
    for s, _ in groups:
        if s not in slots:
            slots.append(s)
    for p in planes:
        if p[0] in ("limb", "neg") and p[1] not in slots:
            slots.append(p[1])
    return FusedPlan(tuple(terms), groups, tuple(planes), tuple(recipes),
                     tuple(slots))


def execute(fp: FusedPlan, program: ir.Program, arrays, params, num_docs,
            n: int, row_offset, interpret: bool):
    """Run the fused kernel; returns the `_run_dense_group_by` output
    contract: (counts_i64, per-agg columns...)."""
    num_segments = program.num_groups + 1
    # runtime scalar vector: [num_docs, row_offset, lo0, hi0, lo1, hi1, ..].
    # Bounds normalize to CLOSED i32 intervals over integer planes:
    #   * float bounds round INWARD (v >= 5.5 ≡ v >= 6; v <= 5.5 ≡ v <= 5;
    #     open bounds v > 5.0 ≡ v >= 6) — matching the two-step path's
    #     float-space compare on integer values
    #   * bounds outside int32 collapse to an EMPTY interval when they
    #     exclude the whole plane (lo > I32_MAX / hi < I32_MIN), never to
    #     a spurious point-match at the clipped extreme
    svals = [jnp.asarray(num_docs, jnp.int64),
             jnp.asarray(row_offset, jnp.int64)]
    for term in fp.terms:
        if term[0] == "runs":
            # dict-id run bounds: already closed i32-safe intervals
            _, _slot, runs_param, n_runs = term
            arr = jnp.asarray(params[runs_param])
            for k in range(2 * n_runs):
                svals.append(arr[k].astype(jnp.int64))
            continue
        _, _slot, lo_p, hi_p, lo_inc, hi_inc = term
        if lo_p is None:
            lo = jnp.int64(_I32_MIN)
        else:
            p = jnp.asarray(params[lo_p])
            if jnp.issubdtype(p.dtype, jnp.inexact):
                lo = (jnp.ceil(p) if lo_inc
                      else jnp.floor(p) + 1).astype(jnp.int64)
            else:
                lo = p.astype(jnp.int64) + (0 if lo_inc else 1)
        if hi_p is None:
            hi = jnp.int64(_I32_MAX)
        else:
            p = jnp.asarray(params[hi_p])
            if jnp.issubdtype(p.dtype, jnp.inexact):
                hi = (jnp.floor(p) if hi_inc
                      else jnp.ceil(p) - 1).astype(jnp.int64)
            else:
                hi = p.astype(jnp.int64) - (0 if hi_inc else 1)
        empty = (lo > _I32_MAX) | (hi < _I32_MIN) | (lo > hi)
        svals.append(jnp.where(empty, jnp.int64(1),
                               jnp.clip(lo, _I32_MIN, _I32_MAX)))
        svals.append(jnp.where(empty, jnp.int64(0),
                               jnp.clip(hi, _I32_MIN, _I32_MAX)))
    scalars = jnp.stack([v.astype(jnp.int32) for v in svals])

    planes_in = tuple(arrays[s] for s in fp.slots)
    sums = _fused_limb_sums(fp, planes_in, scalars, num_segments, n,
                            interpret)

    counts = sums[0]
    outputs = [counts]
    for r in fp.recipes:
        if r[0] == "count":
            outputs.append(counts)
            continue
        _, refs, neg_idx = r
        total = jnp.zeros(counts.shape[0], dtype=jnp.int64)
        for idx, shift in refs:
            total = total + (sums[idx] << shift)
        if neg_idx is not None:
            total = total - (sums[neg_idx] << 32)
        outputs.append(total.astype(jnp.float64))
    return tuple(outputs)


@functools.partial(
    jax.jit, static_argnames=("fp", "num_segments", "n", "interpret"))
def _fused_limb_sums(fp: FusedPlan, planes_in, scalars, num_segments: int,
                     n: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s1, bpsb, nsb, n_pad = mxu_groupby._geometry(n, num_segments)
    if n_pad != n:
        # zero padding is safe: the kernel's row-validity test masks pad
        # rows to the trash slot with zero plane contributions
        planes_in = tuple(jnp.pad(p, (0, n_pad - p.shape[0]))
                          for p in planes_in)
    nb_total = n_pad // (mxu_groupby.SUBLANES * mxu_groupby.LANES)
    planes2 = tuple(
        p.reshape(nb_total, mxu_groupby.SUBLANES, mxu_groupby.LANES)
        for p in planes_in)

    zero = np.int32(0)
    row_spec = pl.BlockSpec(
        (mxu_groupby.G_TILES, mxu_groupby.SUBLANES, mxu_groupby.LANES),
        lambda i, j, s: (i * bpsb + j, zero, zero))
    num_planes = len(fp.planes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nsb, bpsb),
        in_specs=[row_spec] * len(planes2),
        out_specs=pl.BlockSpec((1, num_planes * s1, mxu_groupby.LANES),
                               lambda i, j, s: (i, zero, zero)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, fp, s1, bpsb, num_segments),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (nsb, num_planes * s1, mxu_groupby.LANES), jnp.int32),
        interpret=interpret,
        name="fused_filter_groupby",
    )(scalars, *planes2)
    total = out.astype(jnp.int64).sum(axis=0)
    return total.reshape(num_planes, s1 * mxu_groupby.LANES)[:, :num_segments]


def _kernel(fp: FusedPlan, s1: int, bpsb: int, num_segments: int,
            scal_ref, *rest):
    from jax.experimental import pallas as pl

    LANES = mxu_groupby.LANES
    nb = mxu_groupby.G_TILES * mxu_groupby.SUBLANES
    refs = dict(zip(fp.slots, rest[: len(fp.slots)]))
    out_ref = rest[len(fp.slots)]
    i = pl.program_id(0)
    j = pl.program_id(1)

    # widened (nb, 128) i32 view of each raw plane — ONE load per plane
    loaded = {slot: r[...].reshape(nb, LANES).astype(jnp.int32)
              for slot, r in refs.items()}

    # row validity: global row id vs num_docs (covers segment tail AND the
    # zero padding added by _fused_limb_sums), plus shard row_offset
    base = (i * bpsb + j) * mxu_groupby.BLOCK_ROWS
    rows = (base
            + jax.lax.broadcasted_iota(jnp.int32, (nb, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (nb, LANES), 1))
    m = (rows + scal_ref[1]) < scal_ref[0]
    si = 2  # scalar cursor: [num_docs, row_offset, <term bounds...>]
    for term in fp.terms:
        if term[0] == "runs":
            p = loaded[term[1]]
            tm = jnp.zeros_like(m)
            for _ in range(term[3]):
                tm |= (p >= scal_ref[si]) & (p <= scal_ref[si + 1])
                si += 2
            m &= tm
        else:
            p = loaded[term[1]]
            m &= (p >= scal_ref[si]) & (p <= scal_ref[si + 1])
            si += 2

    gid = jnp.zeros((nb, LANES), dtype=jnp.int32)
    for slot, stride in fp.groups:
        gid = gid + loaded[slot] * jnp.int32(stride)
    gid = jnp.where(m, gid, jnp.int32(num_segments - 1))

    dt = mxu_groupby.PLANE_DTYPE
    bmask = jnp.uint32((1 << mxu_groupby.LIMB_BITS) - 1)
    mats = []
    for pd in fp.planes:
        if pd[0] == "count":
            mats.append(m.astype(dt))
        elif pd[0] == "limb":
            _, slot, shift = pd
            # i32 literal: a Python 0 traces as i64 under x64, which
            # Mosaic cannot narrow
            u = jnp.where(m, loaded[slot], jnp.int32(0)).astype(jnp.uint32)
            # limbs are < 2^8, so the i32 hop is exact (Mosaic has no
            # u32 -> bf16 cast)
            mats.append(((u >> shift) & bmask).astype(jnp.int32).astype(dt))
        else:  # neg
            mats.append((m & (loaded[pd[1]] < 0)).astype(dt))

    mxu_groupby._matmul_tail(gid, mats, s1, out_ref, j)
