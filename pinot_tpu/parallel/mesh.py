"""Multi-device execution: row-sharded segments over a jax Mesh.

This is the capability the reference lacks (SURVEY.md §2.10: "the analogue —
splitting one segment's rows across workers — does not exist in Pinot; the
segment is the atom"). Here one large segment's column planes shard across
TPU cores on a mesh row axis; every device runs the same fused kernel on its
row slice and the per-group partials combine with XLA collectives riding ICI:

    sum/count/sumsq      → psum
    min / max            → pmin / pmax
    distinct occupancy   → any() via pmax
    selection mask       → stays sharded (masks are row-aligned)

A second mesh axis shards *segments* (scatter/gather parallelism, the
reference's per-server fan-out), giving the dp×sp layout used by
__graft_entry__.dryrun_multichip.
"""

from __future__ import annotations

import os
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine import ir
from ..ops.kernels import (PackedOuts, ProgramJit, _pack_flat,
                           _run_program_batch, _run_program_impl, jit_named)

ROW_AXIS = "sp"  # intra-segment row sharding (sequence-parallel analogue)
SEGMENT_AXIS = "dp"  # across segments (data-parallel analogue)


def make_mesh(n_devices: int | None = None, axes=(ROW_AXIS,)) -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    arr = np.array(devices)
    if len(axes) == 2:
        # favor more row-shards than segment-shards
        n = len(devices)
        seg = 2 if n % 2 == 0 and n > 2 else 1
        arr = arr.reshape(seg, n // seg)
    return Mesh(arr, axes)


def _combine_collectives(program: ir.Program, outs: tuple, axis: str) -> tuple:
    """Merge per-shard kernel outputs across the row axis."""
    merged = [jax.lax.psum(outs[0], axis)]
    for agg, o in zip(program.aggs, outs[1:]):
        if agg.kind in ("sum", "sumsq", "count"):
            merged.append(jax.lax.psum(o, axis))
        elif agg.kind == "min":
            merged.append(jax.lax.pmin(o, axis))
        elif agg.kind == "max":
            merged.append(jax.lax.pmax(o, axis))
        elif agg.kind == "distinct_bitmap":
            merged.append(jax.lax.pmax(o.astype(jnp.int32), axis) > 0)
        elif agg.kind in ("value_hist", "hist_fixed"):
            merged.append(jax.lax.psum(o, axis))  # per-(group,bin) counts add
        else:  # pragma: no cover
            raise ValueError(agg.kind)
    return tuple(merged)


def _mask_param_indices(node) -> frozenset:
    """Param slots holding host-evaluated doc-mask planes (ir.MaskParam) —
    those are row-aligned and must shard with the row axis."""
    if node is None:
        return frozenset()
    if isinstance(node, ir.MaskParam):
        return frozenset((node.idx,))
    if isinstance(node, (ir.FAnd, ir.FOr)):
        out = frozenset()
        for c in node.children:
            out |= _mask_param_indices(c)
        return out
    if isinstance(node, ir.FNot):
        return _mask_param_indices(node.child)
    return frozenset()


def slot_specs(slots) -> tuple:
    """PartitionSpecs per kernel input slot: row planes shard on ROW_AXIS,
    dictionaries replicate. Driven by slot KIND, never by shape (a dictionary
    whose cardinality equals the pad bucket must still replicate)."""
    return tuple(P() if kind == "dict" else P(ROW_AXIS) for _col, kind in slots)


@partial(jax.jit, static_argnames=("program", "padded", "mesh", "kinds",
                                   "fused", "lut_meta"))
def _row_sharded_call(program: ir.Program, arrays: tuple, params: tuple, num_docs,
                      padded: int, mesh: Mesh, kinds: tuple,
                      fused: str = "", lut_meta: tuple = ()):
    n_shards = mesh.shape[ROW_AXIS]
    local_n = padded // n_shards
    array_specs = tuple(P() if k == "dict" else P(ROW_AXIS) for k in kinds)
    fp = None
    if fused and program.mode == "group_by":
        # static dtype/ndim analysis — shard dtypes equal global dtypes,
        # so plan once OUTSIDE shard_fn (also scopes check_vma below to
        # programs that genuinely run the fused kernel)
        from ..ops import fused_groupby

        fp = fused_groupby.plan(program, arrays, lut_meta)

    def shard_fn(arrays_l, params_l, num_docs_l):
        idx = jax.lax.axis_index(ROW_AXIS)
        offset = idx.astype(jnp.int32) * jnp.int32(local_n)
        if fp is not None:
            # per-shard fused kernel; table outputs psum over ICI exactly
            # like the two-step path (same output contract)
            from ..ops import fused_groupby

            outs = fused_groupby.execute(
                fp, program, arrays_l, params_l, num_docs_l, local_n,
                offset, interpret=(fused == "interpret"))
            return _combine_collectives(program, outs, ROW_AXIS)
        outs = _run_program_impl(program, arrays_l, params_l, num_docs_l, local_n, offset)
        if program.mode == "selection":
            return outs  # masks stay row-sharded
        return _combine_collectives(program, outs, ROW_AXIS)

    mask_idxs = _mask_param_indices(program.filter)
    param_specs = tuple(
        P(ROW_AXIS) if i in mask_idxs else P() for i in range(len(params)))
    out_specs = P(ROW_AXIS) if program.mode == "selection" else P()
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(array_specs, param_specs, P()),
        out_specs=out_specs,
        # the fused pallas_call's out_shape carries no varying-mesh-axes
        # annotation, so the vma check cannot validate it; keep the check
        # ON for every path that doesn't actually run the fused kernel
        # (it catches missing collective merges at trace time)
        check_vma=fp is None,
    )
    return fn(arrays, params, num_docs)


def run_program_row_sharded(program: ir.Program, arrays: tuple, params: tuple,
                            num_docs, padded: int, mesh: Mesh, slots=None,
                            fused: str = "", lut_meta: tuple = ()):
    """Execute one segment's program with rows sharded across mesh[ROW_AXIS].

    `arrays` are global (padded) planes; `padded` must divide evenly by the
    row-axis size. Group-by/aggregation outputs come back fully combined
    (every device holds the final table — cheap, tables are small). The jitted
    executable is cached on (program, padded, mesh, slot kinds) so repeated
    queries over resident shards skip tracing entirely.
    """
    if program.mode == "group_by_sparse":
        # keyed (sorted) outputs can't psum-merge across shards; the caller
        # runs sparse programs whole-segment and merges at combine instead
        raise ValueError("sparse group-by does not row-shard; run unsharded")
    if any(op.kind == "hist_adaptive" for op in program.aggs):
        # each shard refines a DIFFERENT per-group bucket (data-dependent),
        # so the refined histograms are not psum-mergeable
        raise ValueError("adaptive histograms do not row-shard; run unsharded")
    if program.mv_group_slot is not None:
        # the MV expansion's trailing scanned-docs output has no psum merge
        # wired; run whole-segment (matrix planes also shard per-doc rows
        # only, which _combine_collectives does not model)
        raise ValueError("MV group-by does not row-shard; run unsharded")
    n_shards = mesh.shape[ROW_AXIS]
    assert padded % n_shards == 0, (padded, n_shards)
    kinds = tuple(kind for _col, kind in slots) if slots else tuple(
        "dict" if (a.ndim >= 1 and a.shape[0] != padded) else "ids" for a in arrays)
    return _row_sharded_call(program, arrays, params, jnp.int32(num_docs),
                             padded, mesh, kinds, fused=fused,
                             lut_meta=lut_meta)


# ---------------------------------------------------------------------------
# Segment-axis sharding for batch families (ISSUE 12).
#
# PR-3 stacks a family's segments into [S, N] planes and vmaps one program
# over the stack on a single chip. Here the SAME stacked arrays shard across
# mesh[SEGMENT_AXIS] instead: each device vmaps over its local S/ndev rows,
# so one dispatch runs the whole family on every local chip concurrently.
# Per-row math is byte-for-byte the solo vmap body, which is what makes the
# mesh path bit-identical to `SET meshExecution=false`.
# ---------------------------------------------------------------------------


def mesh_device_count() -> int:
    """Local devices the segment-axis mesh may span, capped by the
    PINOT_TPU_MESH_DEVICES env knob (<=1 disables mesh execution)."""
    n = len(jax.devices())
    cap = os.environ.get("PINOT_TPU_MESH_DEVICES")
    if cap:
        try:
            n = min(n, int(cap))
        except ValueError:
            pass
    return max(1, n)


@lru_cache(maxsize=None)
def segment_mesh(ndev: int) -> Mesh:
    """1-D mesh over the first `ndev` local devices on SEGMENT_AXIS."""
    return Mesh(np.array(jax.devices()[:ndev]), (SEGMENT_AXIS,))


def segment_sharding(ndev: int, ndim: int) -> NamedSharding:
    """NamedSharding splitting the leading (stack) dim across the mesh."""
    return NamedSharding(segment_mesh(ndev),
                         P(SEGMENT_AXIS, *([None] * (ndim - 1))))


def mesh_devices(ndev: int) -> list:
    return list(jax.devices()[:ndev])


def _batch_sharded(program: ir.Program, arrays: tuple, params: tuple,
                   num_docs, padded: int, packed: tuple, ndev: int):
    mesh = segment_mesh(ndev)

    def shard_fn(arrays_l, params_l, num_docs_l):
        # run_program_batch's own body over the (local) stack rows
        return _run_program_batch(program, arrays_l, params_l, num_docs_l,
                                  padded, packed)

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(tuple(P(SEGMENT_AXIS) for _ in arrays),
                  tuple(P(SEGMENT_AXIS) for _ in params),
                  P(SEGMENT_AXIS)),
        out_specs=P(SEGMENT_AXIS),
        # outputs vary per stack row by construction; skip the vma/rep
        # analysis so every program mode the solo vmap supports shards
        check_vma=False,
    )
    return fn(arrays, params, num_docs)


# `jit_scan_<label>`, as the solo and the batched program are named
_batch_sharded_call = ProgramJit(_batch_sharded, "scan",
                                 ("program", "padded", "packed", "ndev"))


def run_program_batch_sharded(program: ir.Program, arrays: tuple, params: tuple,
                              num_docs, padded: int, ndev: int,
                              packed: tuple = ()):
    """run_program_batch with the stack dim sharded over mesh[SEGMENT_AXIS].

    `arrays`/`params`/`num_docs` are the family stacks padded to a multiple
    of `ndev` rows (ragged remainders repeat the last member with num_docs=0
    — the impl's row-validity mask makes those slots contribute nothing).
    Outputs come back [S_pad, ...] sharded on SEGMENT_AXIS; callers slice or
    gather on device (`pack_outputs_gathered` / `gather_outputs`).
    """
    return _batch_sharded_call(program, tuple(arrays), tuple(params),
                               num_docs, padded, tuple(packed), ndev)


def _pack_sliced(outs: tuple, s_real: int):
    # drop the ragged pad rows on device, then byte-pack exactly like the
    # solo path so the host sees identical flat bytes
    return _pack_flat(tuple(o[:s_real] for o in outs))


def _pack_jit(fn, label: str, static: tuple):
    """The mesh's output packs, named `jit_pack_<label>` like the solo
    pack (kernels.pack_outputs)."""
    return jit_named(fn, f"pack_{label}" if label else fn.__name__,
                     static_argnames=static)


def pack_outputs_gathered(outs: tuple, s_real: int,
                          label: str = "") -> PackedOuts:
    """Device-side cross-chip combine for the packed (dense) path: slice the
    pad rows, byte-pack on device, and commit the flat to device 0 so it
    concatenates with solo packs and crosses to host exactly once."""
    metas = [(np.dtype(str(o.dtype)), (s_real,) + tuple(o.shape[1:]))
             for o in outs]
    pack = _pack_jit(_pack_sliced, label, ("s_real",))
    flat = jax.device_put(pack(tuple(outs), s_real), jax.devices()[0])
    return PackedOuts(flat, metas)


def _pack_collective(outs: tuple, s_real: int, ndev: int):
    mesh = segment_mesh(ndev)

    def shard_fn(outs_l):
        # all-gather the family stacks over ICI so every chip holds the
        # full [S_pad, ...] outputs, then slice + byte-pack locally — the
        # byte order is exactly _pack_sliced's, so the host decode is shared
        gathered = tuple(
            jax.lax.all_gather(o, SEGMENT_AXIS, axis=0, tiled=True)
            for o in outs_l)
        return _pack_flat(tuple(g[:s_real] for g in gathered))

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(tuple(P(SEGMENT_AXIS) for _ in outs),),
        out_specs=P(),
        # the gathered pack is replicated by construction; skip the rep
        # analysis for the same reason _batch_sharded_call does
        check_vma=False,
    )
    return fn(tuple(outs))


def pack_outputs_collective(outs: tuple, s_real: int, ndev: int,
                            label: str = "") -> PackedOuts:
    """Mesh-collective variant of pack_outputs_gathered: the shuffle to one
    chip happens INSIDE the sharded program (all_gather over the segment
    axis) and every chip byte-packs the full stack, instead of funneling raw
    outputs to device 0 with per-output device_puts first. One collective +
    one pack kernel; the flat is replicated, so the host still crosses once."""
    metas = [(np.dtype(str(o.dtype)), (s_real,) + tuple(o.shape[1:]))
             for o in outs]
    pack = _pack_jit(_pack_collective, label, ("s_real", "ndev"))
    flat = jax.device_put(pack(tuple(outs), s_real, ndev), jax.devices()[0])
    return PackedOuts(flat, metas)


def gather_outputs(outs: tuple, s_real: int) -> tuple:
    """Cross-chip gather for the raw path (sparse device combine): commit
    every [S_pad, ...] output to device 0 over ICI — no host crossing — so
    downstream per-row slices and `merge_group_tables` colocate
    with device-0-resident dictionaries."""
    dev0 = jax.devices()[0]
    return tuple(jax.device_put(o[:s_real], dev0) for o in outs)


def shard_segment_arrays(arrays: tuple, mesh: Mesh, padded: int, slots=None):
    """Pre-place padded planes with row sharding so repeated queries reuse
    device-resident shards (the multi-device HBM segment cache)."""
    if slots is not None:
        specs = slot_specs(slots)
    else:
        specs = tuple(P(ROW_AXIS) if a.ndim >= 1 and a.shape[0] == padded else P()
                      for a in arrays)
    return tuple(
        jax.device_put(a, NamedSharding(mesh, s)) for a, s in zip(arrays, specs)
    )
