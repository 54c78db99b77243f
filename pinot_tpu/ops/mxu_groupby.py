"""Dense group-by sums on the MXU: Kronecker-factored one-hot matmuls.

The TPU-first answer to the reference's `DefaultGroupByExecutor` hot loop
(pinot-core/.../query/aggregation/groupby/DefaultGroupByExecutor.java:191):
instead of scatter-adds (7-8ns/update on the TPU scatter unit — a 100M-row
group-by with several payload planes costs seconds) or hash maps, the dense
group key is split into a 7-bit low half and a high half, and the whole
reduction becomes a matmul chain the systolic array executes near peak:

    out[hi, p*128+lo]  +=  oh_hi[hi, row] @ (plane_p[row] * oh_lo[row, lo])

where ``oh_hi`` is the one-hot of ``gid >> 7`` (S1 x B) and the right operand
stacks every payload plane scaled by the one-hot of ``gid & 127`` (B x P*128).
One MXU pass of (S1 x B) @ (B x P*128) replaces P scatters over B rows; for
S1 <= 128 the cost per row is *independent of the group count*, and all
payload planes ride the same pass.

Exactness: payloads must be small non-negative integers. The default plane
dtype is **int8 with 7-bit limbs** (values in [0, 127]): v5e executes s8xs8
matmuls at twice the bf16 rate with native i32 accumulation, and the planes
cost half the HBM bandwidth of bf16. Per-superblock i32 accumulation is
exact (SB_ROWS * 127 < 2^31); superblock partials are summed in int64
outside the kernel. Setting PINOT_TPU_MXU_INT8=0 falls back to bf16 planes
with 8-bit limbs ([0, 255] — bf16-exact; per-block f32 accumulation exact
because B * 255 < 2^24).

Masked rows must already be routed to a trash slot by the caller (the dense
planner convention: gid == num_segments - 1), with zeroed payloads.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

# exact i64 totals (engine-wide invariant, see ops/kernels.py)
jax.config.update("jax_enable_x64", True)

LANES = 128
SUBLANES = 8
# row-blocks per grid step: each step reduces G*8*128 rows with one batched
# MXU pass (batch dim G*8, contraction dim 128). G trades VMEM for fewer
# grid steps.
G_TILES = 4
BLOCK_ROWS = G_TILES * SUBLANES * LANES  # 4096
# superblock = rows whose limb sums stay exact in the i32 accumulator:
# SB_ROWS * 255 < 2^31
SB_BLOCKS = 256
SB_ROWS = SB_BLOCKS * BLOCK_ROWS  # ~1M
# above this many group slots the (S1, P*128) accumulator stops fitting
# comfortably in VMEM next to the one-hot operands
MAX_GROUPS = 1 << 15

# int8 MXU path (2x matmul rate + half the plane bandwidth on v5e).
# PINOT_TPU_MXU_INT8=0 reverts to bf16/8-bit limbs.
_INT8 = os.environ.get("PINOT_TPU_MXU_INT8", "1") != "0"
PLANE_DTYPE = jnp.int8 if _INT8 else jnp.bfloat16
LIMB_BITS = 7 if _INT8 else 8
# int8 planes cost half the VMEM of bf16 AND 7-bit limbs need one more
# plane per signed-i32 sum (5+neg vs 4+neg) — scale the plane budget so a
# 3x signed-SUM query (1 + 3*6 = 19 planes) still rides one MXU pass
MAX_PLANES = 24 if _INT8 else 16


def backend_platform() -> str:
    """The default jax backend's platform. A backend that fails to
    initialise raises: a dead accelerator is an error, not a reason to
    pick the CPU branch."""
    return jax.default_backend()


def supports(num_segments: int, num_planes: int) -> bool:
    if not (0 < num_planes <= MAX_PLANES and num_segments <= MAX_GROUPS):
        return False
    # accumulator block is (num_planes * s1, 128) i32 — bound the product
    # so it stays ~2 MB of VMEM next to the one-hot operands
    s1 = max(1, -(-num_segments // LANES))
    return num_planes * s1 <= 4096


def limb_sums(planes, gid, num_segments: int, *, interpret: bool = False):
    """Sum each plane per group: planes P x (n,) of PLANE_DTYPE holding
    integer limb values in [0, 2**LIMB_BITS - 1] (int8 planes: [0, 127];
    bf16 planes: [0, 255]), gid (n,) int32 in [0, num_segments); returns
    (P, num_segments) int64. Uses the Pallas MXU kernel on TPU, a
    kron-factored XLA matmul elsewhere (interpret=True forces the Pallas
    kernel in interpret mode for kernel-parity tests)."""
    assert supports(num_segments, len(planes))
    if interpret or backend_platform() == "tpu":
        return _pallas_limb_sums(tuple(planes), gid, num_segments,
                                 interpret=interpret)
    return _xla_limb_sums(tuple(planes), gid, num_segments)


# -- shared geometry ---------------------------------------------------------


def _geometry(n: int, num_segments: int):
    s1 = max(1, -(-num_segments // LANES))
    blocks = max(1, -(-n // BLOCK_ROWS))
    bpsb = min(SB_BLOCKS, blocks)
    nsb = -(-blocks // bpsb)
    n_pad = nsb * bpsb * BLOCK_ROWS
    return s1, bpsb, nsb, n_pad


def _pad_inputs(planes, gid, num_segments, n_pad):
    n = gid.shape[0]
    if n_pad != n:
        # padding rows join the caller's trash slot with zero payloads
        gid = jnp.pad(gid, (0, n_pad - n),
                      constant_values=np.int32(num_segments - 1))
        planes = tuple(jnp.pad(p, (0, n_pad - n)) for p in planes)
    return planes, gid


# -- Pallas TPU kernel -------------------------------------------------------


def _kernel(s1: int, num_planes: int, gid_ref, *rest):
    from jax.experimental import pallas as pl

    plane_refs = rest[:num_planes]
    out_ref = rest[num_planes]
    j = pl.program_id(1)
    nb = G_TILES * SUBLANES  # batch dim of the MXU pass
    # leading-dim collapse (G, 8, 128) -> (G*8, 128): pure addressing, no
    # sublane/lane relayout
    g = gid_ref[...].reshape(nb, LANES)
    mats = [pr[...].reshape(nb, LANES) for pr in plane_refs]
    _matmul_tail(g, mats, s1, out_ref, j)


def _matmul_tail(g, mats, s1: int, out_ref, j):
    """The one-hot matmul chain shared by the pre-materialized-plane kernel
    (`_kernel`) and the fused filter+gid+limb kernel
    (ops/fused_groupby.py): g (nb, 128) int32 gids, mats P x (nb, 128)
    PLANE_DTYPE limb values, accumulated into out_ref block (1, P*s1, 128)
    i32 across the j grid axis."""
    from jax.experimental import pallas as pl

    num_planes = len(mats)
    # int8 planes ride the s8xs8->i32 MXU mode (2x bf16 rate on v5e);
    # bf16 planes keep the f32-accumulating dot
    int8 = mats[0].dtype == jnp.int8
    oh_dt = jnp.int8 if int8 else jnp.bfloat16
    acc_dt = jnp.int32 if int8 else jnp.float32
    nb = g.shape[0]
    hi = g >> 7
    lo = g & (LANES - 1)

    def mid(x, m):
        # (nb, LANES) -> (nb, m, LANES): stride-0 sublane broadcast; rows
        # stay on the minor (lane) dim — the only relayout Mosaic rejects
        # is moving lanes off minor
        return jax.lax.broadcast_in_dim(x, (nb, m, LANES), (0, 2))

    # Planes fold into the MATMUL'S M DIMENSION (one (nb, Pg*s1, C) lhs
    # against a SHARED lo one-hot rhs) rather than into N as P separate
    # matmuls: M = Pg*s1 fills the systolic array's 128-row tiles ~2x
    # better than s1 alone (s1 is ~55 for a 7K-group query — a 43% fill),
    # and the rhs one-hot + per-plane multiplies collapse into one
    # compare + P selects. Same MAC count, much higher MXU occupancy.
    # Planes chunk so the lhs + dot output stay within VMEM at the
    # largest supported s1 (256). The binding buffer is the i32/f32 dot
    # OUTPUT (nb, Pg*s1, 128) — 4 bytes per element on BOTH dtypes — so
    # the Pg*s1 <= 384 budget holds for int8 too (a larger int8 chunk
    # would only shrink the 1-byte lhs while doubling the accumulator).
    # one-hot + multiply (not a bool mask + select: Mosaic rejects
    # the i1 relayout when the mask is reused across plane chunks)
    # the v5e has no i8 vector multiply: int8 limbs scale the one-hot in
    # i32 (the compare's own width) and narrow the product; bf16 limbs
    # multiply in bf16
    mul_dt = jnp.int32 if int8 else oh_dt
    oh_hi = (jax.lax.broadcasted_iota(jnp.int32, (nb, s1, LANES), 1)
             == mid(hi, s1)).astype(mul_dt)
    rhs = (jax.lax.broadcasted_iota(jnp.int32, (nb, LANES, LANES), 1)
           == mid(lo, LANES)).astype(oh_dt)  # (nb, L, C)
    pg = max(1, 384 // s1)
    # both operands keep the contraction (row) dim minor — an NT matmul,
    # the same shape attention uses for q @ k^T (Mosaic supports exactly
    # one contracting dim, so nb stays a batch dim and the batch outputs
    # sum after). Accumulation is exact on both paths: i32 native for s8
    # dots; f32 for bf16 (each dot sums 128 values <= 255 and the batch
    # sum stays below 2^24).
    dn = (((2,), (2,)), ((0,), (0,)))
    parts = []
    for start in range(0, num_planes, pg):
        lhs = jnp.concatenate(
            [(oh_hi * mid(pm.astype(mul_dt), s1)).astype(oh_dt)
             for pm in mats[start:start + pg]], axis=1)
        out = jax.lax.dot_general(lhs, rhs, dn,
                                  preferred_element_type=acc_dt)
        # dtype pinned: under jax_enable_x64 an i32 sum promotes to i64,
        # which Mosaic has no lowering for
        parts.append(out.sum(axis=0, dtype=acc_dt))  # (Pg*s1, L)
    part = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]

    @pl.when(j == 0)
    def _init():
        out_ref[0] = part.astype(jnp.int32)

    @pl.when(j != 0)
    def _acc():
        out_ref[0] = out_ref[0] + part.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def _pallas_limb_sums(planes, gid, num_segments: int, interpret: bool = False):
    from jax.experimental import pallas as pl

    num_planes = len(planes)
    n = gid.shape[0]
    s1, bpsb, nsb, n_pad = _geometry(n, num_segments)
    planes, gid = _pad_inputs(planes, gid, num_segments, n_pad)

    nb = n_pad // (SUBLANES * LANES)
    gid2 = gid.reshape(nb, SUBLANES, LANES)
    planes2 = [p.reshape(nb, SUBLANES, LANES) for p in planes]

    zero = np.int32(0)  # literal 0 traces as i64 under x64; Mosaic needs i32
    row_spec = pl.BlockSpec((G_TILES, SUBLANES, LANES),
                            lambda i, j: (i * bpsb + j, zero, zero))
    out = pl.pallas_call(
        functools.partial(_kernel, s1, num_planes),
        grid=(nsb, bpsb),
        in_specs=[row_spec] * (1 + num_planes),
        out_specs=pl.BlockSpec((1, num_planes * s1, LANES),
                               lambda i, j: (i, zero, zero)),
        out_shape=jax.ShapeDtypeStruct((nsb, num_planes * s1, LANES),
                                       jnp.int32),
        interpret=interpret,
        name="mxu_limb_groupby",
    )(gid2, *planes2)

    # (nsb, P*S1, 128) --sum--> (P*S1, 128) --> (P, S1*128) --> trim
    total = out.astype(jnp.int64).sum(axis=0)
    return total.reshape(num_planes, s1 * LANES)[:, :num_segments]


# -- XLA fallback (CPU / virtual meshes) -------------------------------------


@functools.partial(jax.jit, static_argnames=("num_segments",))
def _xla_limb_sums(planes, gid, num_segments: int):
    stacked = jnp.stack(planes, axis=0)  # (P, n): n minor — no lane padding
    sums = jax.vmap(
        lambda p: jax.ops.segment_sum(p.astype(jnp.float64), gid,
                                      num_segments=num_segments))(stacked)
    return sums.astype(jnp.int64)
