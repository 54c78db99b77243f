"""The jitted per-segment kernel interpreter.

This one function replaces the reference's entire per-segment execution stack
— filter operators, DocIdSet iteration, DataFetcher/ProjectionOperator and
DefaultGroupByExecutor.aggregateGroupBySV
(pinot-core/.../groupby/DefaultGroupByExecutor.java:191-218) — with a single
fused XLA computation per (program, segment-shape):

    mask  = filter tree as boolean vector algebra        (VPU, fused)
    gid   = Σ dict_ids[d] * stride[d]  (+ trash bucket for masked rows)
    out_k = segment_sum / segment_min / segment_max per aggregation

Design notes (SURVEY.md §7):
- masked fixed-shape execution: all rows compute, invalid rows route to a
  trash group that is sliced off on host. No dynamic shapes anywhere.
- `program` is a static jit arg (hashable IR, engine/ir.py); literals arrive
  via `params`, so repeated query shapes reuse the compiled executable.
- int64/float64 accumulation for exact parity with the reference's
  long/double agg results (jax x64 enabled at package import).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from functools import partial, wraps

import jax
import jax.numpy as jnp
import numpy as np

from ..engine import ir
from ..spi.trace import DEVICE_COPY, FETCH_DECODE, TRACING
from . import mxu_groupby

jax.config.update("jax_enable_x64", True)


# Longest dictionary plane (executor._dict_pad bucket) decoded by a select
# chain; a longer one is gathered. On a v5e, Q1.1 over 16 x 2^22 rows: the
# chain takes 13.4 ms at 16 entries, 19.9 at 256, 45.2 at 1,024 and 143.3 at
# 4,096 where the gather takes 677-801 ms, so the chain's COMPILE time sets
# the limit: 3.2 s at 256, 9.5 s at 512, 14.4 s at 1,024, 91.6 s at 4,096
# for each DictGather of a program (the gather: 1.6 s), paid by the first
# query of a family. PERF.md section 6, PR 26, has the sweep.
DICT_SELECT_MAX = 256
# Selects per XLA fusion of the chain. The compiler's time grows faster than
# a fusion's length: 256 selects in one fusion cost it 97 s (sandbox), in
# fusions of 64 10.7 s, of 32 3.2 s (chip), for a run time within 9 %.
_DICT_SELECT_FUSE = 32


def dict_lookup_form(plane_len: int) -> str:
    """How a dictionary plane of `plane_len` entries is decoded: the one
    rule that `_dict_lookup` lowers by and `dict_lookups` counts by. (An
    empty plane has no entry to start a chain from.)"""
    return "select" if 0 < plane_len <= DICT_SELECT_MAX else "gather"


def _dict_lookup(table, ids):
    """table[ids], the only lowering of ir.DictGather. The TPU gathers one
    row at a time (9.7 ns a row, whatever the table's size), so a small
    table is looked up in registers (`_select_chain`). Ids outside
    [0, len) — row padding, masked by the caller — read entry 0 there and
    a clamped entry in the gather."""
    if dict_lookup_form(table.shape[0]) == "gather":
        return table[ids]
    return _select_chain(table, ids)


def _select_chain(table, ids):
    """table[ids] by a chain of selects: the table's entries are static
    slices (one scalar a segment under the family's vmap), and XLA fuses
    the chain into what reads it, as it does the filter. A select is exact
    for every dtype (a one-hot product would turn 0 * inf into NaN). Ids
    outside [0, len) read entry 0."""
    out = jnp.broadcast_to(table[0], ids.shape)
    for k in range(1, table.shape[0]):
        out = jnp.where(ids == k, table[k], out)
        if k % _DICT_SELECT_FUSE == 0:
            # ends the fusion: the plane so far goes through HBM once
            out = jax.lax.optimization_barrier(out)
    return out


# Longest boolean LUT (`ir.Lut`: an IN list, NOT IN, LIKE, an OR of EQs
# over a dictionary column; its length is the cardinality + 1) tested as
# bits in registers; a longer one is gathered. PERF.md section 6 has the
# sweep (`python -m pinot_tpu.tools.lut_filter_sweep`).
LUT_BITS_MAX = 4096


def lut_filter_form(lut_len: int) -> str:
    """How an `ir.Lut` over a LUT of `lut_len` entries is lowered: the one
    rule that `_lut_mask` lowers by and `lut_gathers` counts by."""
    return "bits" if 0 < lut_len <= LUT_BITS_MAX else "gather"


def _lut_mask(lut, ids):
    """lut[ids] for a boolean LUT, the only lowering of ir.Lut. The TPU
    gathers one row at a time, so a LUT of up to LUT_BITS_MAX entries is
    folded, inside the program, into W = ceil(len / 32) uint32 words (bit
    b of word w is entry 32 w + b; O(len) a segment, not a row), and each
    row picks its word by `_select_chain` over the W words and tests its
    bit: W - 1 selects and a few shifts a row, fused with the rest of the
    filter. Ids outside [0, len) — row padding, masked by the caller — read
    some bit here and a clamped entry in the gather."""
    n = lut.shape[0]
    if lut_filter_form(n) == "gather":
        return lut[ids]
    w = -(-n // 32)
    bits = jnp.pad(lut.astype(jnp.uint32), (0, 32 * w - n)).reshape(w, 32)
    words = (bits << jnp.arange(32, dtype=jnp.uint32)).sum(
        axis=1, dtype=jnp.uint32)
    word = _select_chain(words, ids >> 5)
    return ((word >> (ids & 31).astype(jnp.uint32)) & 1).astype(bool)


def lut_gathers(program: ir.Program, params) -> int:
    """How many of the program's Lut nodes lower as a gather, given the
    params a dispatch feeds it (solo or stacked: the LUT's length is the
    last dim)."""
    return sum(lut_filter_form(params[node.lut_param].shape[-1]) == "gather"
               for node in ir.luts(program))


def dict_lookups(program: ir.Program, arrays) -> str:
    """`select:<n>,gather:<m>`: how many of the program's DictGather nodes
    decode by each form, given the planes a dispatch feeds it (solo or
    stacked: the plane's length is the last dim)."""
    forms = [dict_lookup_form(arrays[g.dict_slot].shape[-1])
             for g in ir.dict_gathers(program)]
    return f"select:{forms.count('select')},gather:{forms.count('gather')}"


# Most groups (the trash slot apart) of a dense table whose MIN and MAX are
# masked reductions; a larger table keeps the scatter. On a v5e, a MIN and a
# MAX over 16 x 2^22 rows by scatter take 66-74 ms a segment whatever the
# table's size (690-790 ms for float64 values: 64-bit scatters are
# emulated); the reduction costs a compare, a select and a min a row AND
# group: 0.9 ms a segment at 8 groups, 1.6 at 128, 2.7 at 256, 4.2 at 512,
# 14.4 at 2,048 (int32; float32 a little under). Set at the largest swept
# size where the reduction is ahead 2 x or more both in a first use
# (compile and one run: 1.65-1.72 s against 0.65-0.72 at 256) and in the
# steady state (26-32 x there); at 512 one first use of four read 1.99 x.
# PERF.md section 6, PR 32, has the sweep
# (`python -m pinot_tpu.tools.minmax_sweep`).
MINMAX_REDUCE_MAX_GROUPS = 256


def min_max_form(num_groups: int) -> str:
    """How a dense group-by of `num_groups` groups takes a MIN or a MAX:
    the one rule that `_run_agg` lowers by and `min_max_forms` counts by."""
    return "reduce" if 0 < num_groups <= MINMAX_REDUCE_MAX_GROUPS \
        else "scatter"


def min_max_forms(program: ir.Program) -> str:
    """`reduce:<n>,scatter:<m>`: how many of the program's MIN and MAX
    aggregations take each form. Only the dense group-by chooses: the
    ungrouped, sort-based and selection programs read 0 and 0."""
    forms = [min_max_form(program.num_groups) for agg in program.aggs
             if agg.kind in ("min", "max")] \
        if program.mode == "group_by" else []
    return f"reduce:{forms.count('reduce')},scatter:{forms.count('scatter')}"


def group_table_form(program: ir.Program) -> str:
    """Which group table a program fills, from its static fields: `limb`
    (a dense table inside `mxu_groupby.MAX_GROUPS`, the planner's own test:
    its COUNT and 32-bit SUMs ride the limb kernel), `dense` (a larger one:
    a scatter per limb), `sorted` / `presorted` (the sort-based kernel,
    rows sorted by key or read in the segment's own order), `none` (no
    group-by)."""
    if program.mode == "group_by_sparse":
        return "presorted" if program.keys_presorted else "sorted"
    if program.mode != "group_by":
        return "none"
    return "limb" if mxu_groupby.supports(program.num_groups + 1, 1) \
        else "dense"


def sum_limbs(agg: ir.AggOp, int32_value: bool, n: int, used: int):
    """How a dense table's SUM rides the limb pass, with `used` planes
    already taken: (shifts, neg_bit, wide), the starting bits of its 7-bit
    digit planes, the bit its negative-count plane is weighed at (None: no
    such plane) and whether the value is wider than int32; or None where
    it is left to a scatter (`_run_agg`). A value that fits int32 (an
    int32 plane, or the planner's bounds) takes `_limb_shifts`' digits; a
    wider one with bounds takes the digits of its 64-bit two's complement
    up to the bits the bounds need, provided `n` rows of it cannot reach
    2^63 (4,194,304 rows x 2^37 stay under it), so the int64 total is
    exact; any other value (a float, an unbounded 64-bit integer) is
    scattered."""
    if agg.kind != "sum":
        return None
    lo, hi = agg.vmin, agg.vmax
    bounded = lo is not None and hi is not None
    if int32_value or (bounded and lo >= _I32_MIN and hi <= _I32_MAX):
        shifts, nonneg = _limb_shifts(lo, hi, mxu_groupby.LIMB_BITS)
        neg_bit, wide = None if nonneg else 32, False
    elif bounded and n * max(-lo, hi) < 1 << 63:
        nbits = hi.bit_length() if lo >= 0 else 1 + max(
            hi.bit_length(), (-lo - 1).bit_length())
        shifts = list(range(0, max(1, nbits), mxu_groupby.LIMB_BITS))
        neg_bit, wide = None if lo >= 0 else nbits, True
    else:
        return None
    if used + len(shifts) + (neg_bit is not None) > mxu_groupby.MAX_PLANES:
        return None
    return shifts, neg_bit, wide


def dense_sum_limbs(program: ir.Program, n: int, int32_value) -> tuple:
    """(per aggregation `sum_limbs`' answer in turn, the planes of the
    pass): the count plane first, then each SUM's digits while they fit;
    no pass at all (every answer None, 0 planes) where the planes and the
    slots outgrow the limb kernel (`mxu_groupby.supports`).
    `int32_value(agg)` says whether an unbounded SUM's value is an int32
    plane. The one rule that `_run_dense_group_by` builds by and
    `limb_forms` counts by."""
    used, out = 1, []
    for agg in program.aggs:
        unbounded = agg.vmin is None or agg.vmax is None
        limbs = sum_limbs(agg, agg.kind == "sum" and unbounded
                          and int32_value(agg), n, used)
        if limbs is not None:
            used += len(limbs[0]) + (limbs[1] is not None)
        out.append(limbs)
    if not mxu_groupby.supports(program.num_groups + 1, used):
        return [None] * len(out), 0
    return out, used


def limb_forms(program: ir.Program, n: int, arrays) -> tuple:
    """(limb planes, SUM scatters) of a program from its static fields and
    the planes a dispatch feeds it: the int8 planes its dense table's MXU
    pass carries (the count plane and every SUM's digits; 0 where no limb
    pass runs), and how many SUM-kind aggregations are left to a scatter,
    exact 32-bit limb scatters or a float64 one (a dense table's that the
    pass does not take; a sorted table's 32-bit sums whose prefix
    differences could pass 2^53, `_prefix_exact`). An unbounded SUM
    counts as an int32 value where its plane is one (a column, a
    dictionary's values)."""
    def int32_value(agg):
        node = agg.vexpr
        slot = node.slot if isinstance(node, ir.Col) else \
            node.dict_slot if isinstance(node, ir.DictGather) else None
        return slot is not None and arrays[slot].dtype == jnp.int32

    sums = [a for a in program.aggs if a.kind in ("sum", "sumsq")]
    if program.mode == "group_by":
        limbs, planes = dense_sum_limbs(program, n, int32_value)
        return planes, sum(1 for a, lb in zip(program.aggs, limbs)
                           if a.kind in ("sum", "sumsq") and lb is None)
    if program.mode == "group_by_sparse":
        scattered = 0
        for a in sums:
            if a.kind == "sum" and (
                    int32_value(a) if a.vmin is None or a.vmax is None
                    else a.vmin >= _I32_MIN and a.vmax <= _I32_MAX):
                scattered += not _prefix_exact(a, n)
        return 0, scattered
    return 0, 0


def signed_sums(program: ir.Program) -> int:
    """How many of a program's SUMs have static bounds below zero, so that
    they carry the signed recipe: a negative-count plane on the limb pass
    (`_limb_recipe`), a signed payload through a sorted table. From the
    program's static fields, no sync."""
    return sum(1 for a in program.aggs
               if a.kind == "sum" and a.vmin is not None and a.vmin < 0)


def _eval_ring32(node: ir.ValueExpr, arrays, params):
    """`node`'s value modulo 2^32, as int32, in 32-bit arithmetic: +, - and
    * wrap in two's complement, so a tree of them over integer leaves,
    each taken modulo 2^32, agrees with the exact value modulo 2^32, and is
    that value wherever it fits int32 (the planner's bounds say where).
    The TPU has no 64-bit ALU: `(100 - disc) * price` over int64 literals
    is a chain of emulated 64-bit ops, here one 32-bit multiply. None
    where a leaf is not an integer."""
    if isinstance(node, ir.Bin) and node.op in ("add", "sub", "mul"):
        a = _eval_ring32(node.a, arrays, params)
        b = _eval_ring32(node.b, arrays, params)
        return None if a is None or b is None else _BIN_OPS[node.op](a, b)
    v = _eval_value(node, arrays, params)
    return v.astype(jnp.int32) if jnp.issubdtype(v.dtype, jnp.integer) \
        else None


def _sum_value32(agg: ir.AggOp, v, arrays, params):
    """A SUM's value as int32 where it fits (`_fits_i32`): the plane as it
    is, or an integer expression evaluated in 32 bits (`_eval_ring32`)."""
    if v.dtype == jnp.int32:
        return v
    narrow = _eval_ring32(agg.vexpr, arrays, params)
    return v.astype(jnp.int32) if narrow is None else narrow


def _mul_words(a, b):
    """(lo, hi) uint32 words of the exact product of two int32 planes, by
    16-bit halves: four 32-bit multiplies and the carries, where XLA
    emulates an s64 multiply. Signed operands: a*b = ua*ub - 2^32 (ub [a<0]
    + ua [b<0]) modulo 2^64, so only the high word is corrected."""
    ua, ub = a.astype(jnp.uint32), b.astype(jnp.uint32)
    half = jnp.uint32(0xFFFF)
    a0, a1, b0, b1 = ua & half, ua >> 16, ub & half, ub >> 16
    low, mid0, mid1, high = a0 * b0, a0 * b1, a1 * b0, a1 * b1
    mid = mid0 + mid1
    lo = low + (mid << 16)
    hi = (high + (mid >> 16) + ((mid < mid0).astype(jnp.uint32) << 16)
          + (lo < low).astype(jnp.uint32))
    zero = jnp.uint32(0)
    hi = hi - jnp.where(a < 0, ub, zero) - jnp.where(b < 0, ua, zero)
    return lo, hi


def _value_words(node: ir.ValueExpr, arrays, params):
    """(lo, hi) uint32 words of an integer value modulo 2^64: a `mulw` of
    two int32 factors by `_mul_words`, anything else from its int64
    plane."""
    if isinstance(node, ir.Bin) and node.op == "mulw":
        a = _eval_ring32(node.a, arrays, params)
        b = _eval_ring32(node.b, arrays, params)
        if a is not None and b is not None:
            return _mul_words(a, b)
    v = _eval_value(node, arrays, params).astype(jnp.int64)
    return v.astype(jnp.uint32), (v >> 32).astype(jnp.uint32)


def _digit(lo, hi, shift: int, bits: int):
    """Bits [shift, shift + bits) of the 64-bit value (hi, lo)."""
    if shift + bits <= 32:
        word = lo >> shift
    elif shift >= 32:
        word = hi >> (shift - 32)
    else:
        word = (lo >> shift) | (hi << (32 - shift))
    return word & jnp.uint32((1 << bits) - 1)


def _eval_value(node: ir.ValueExpr, arrays, params):
    if isinstance(node, ir.Col):
        return arrays[node.slot]
    if isinstance(node, ir.IdsCol):
        return arrays[node.slot]
    if isinstance(node, ir.DictGather):
        return _dict_lookup(arrays[node.dict_slot], arrays[node.ids_slot])
    if isinstance(node, ir.ConstParam):
        return params[node.idx]
    if isinstance(node, ir.ParamGather):
        ids = _eval_value(node.ids, arrays, params)
        return params[node.param_idx][ids]
    if isinstance(node, ir.Bin):
        a = _eval_value(node.a, arrays, params)
        b = _eval_value(node.b, arrays, params)
        return _BIN_OPS[node.op](a, b)
    if isinstance(node, ir.Un):
        return _UN_OPS[node.op](_eval_value(node.a, arrays, params))
    if isinstance(node, ir.Cast):
        return _eval_value(node.a, arrays, params).astype(_CAST_DTYPES[node.to])
    if isinstance(node, ir.Where):
        return jnp.where(
            _eval_value(node.cond, arrays, params),
            _eval_value(node.a, arrays, params),
            _eval_value(node.b, arrays, params),
        )
    if isinstance(node, ir.NullCol):
        return arrays[node.null_slot]
    if isinstance(node, ir.FilterVal):
        # n=1 for constant leaves: a (1,) mask broadcasts against (n,)
        # operands in the Where wrap
        return _eval_filter(node.filter, arrays, params, 1)
    if isinstance(node, ir.MvLutReduce):
        if node.op == "count":  # non-pad slots per doc; no LUT gather
            return (arrays[node.ids_slot] != node.card).sum(
                axis=1).astype(jnp.int32)
        vals = params[node.lut_param][arrays[node.ids_slot]]  # (n, max_mv)
        if node.op == "sum":
            return vals.sum(axis=1)
        if node.op == "min":
            return vals.min(axis=1)
        return vals.max(axis=1)
    raise TypeError(f"unknown value node {node}")


_BIN_OPS = {
    "add": jnp.add,
    "sub": jnp.subtract,
    "mul": jnp.multiply,
    # a product of two factors that fit int32 and whose result may not
    # (the planner's bounds): exact in int64 (`_value_words` forms its
    # words in 32-bit multiplies)
    "mulw": lambda a, b: a.astype(jnp.int64) * b.astype(jnp.int64),
    "div": jnp.true_divide,
    "fdiv": jnp.floor_divide,
    "mod": jnp.mod,
    "pow": jnp.power,
    "eq": lambda a, b: a == b,
    "ne": lambda a, b: a != b,
    "lt": lambda a, b: a < b,
    "le": lambda a, b: a <= b,
    "gt": lambda a, b: a > b,
    "ge": lambda a, b: a >= b,
    "and": jnp.logical_and,
    "or": jnp.logical_or,
    "min": jnp.minimum,
    "max": jnp.maximum,
}

_UN_OPS = {
    "neg": jnp.negative,
    "abs": jnp.abs,
    "not": jnp.logical_not,
    "exp": jnp.exp,
    "ln": jnp.log,
    "log10": jnp.log10,
    "log2": jnp.log2,
    "sqrt": jnp.sqrt,
    "ceil": jnp.ceil,
    "floor": jnp.floor,
    "sign": jnp.sign,
}

_CAST_DTYPES = {
    "INT": jnp.int32,
    "LONG": jnp.int64,
    "FLOAT": jnp.float32,
    "DOUBLE": jnp.float64,
    "BOOLEAN": jnp.bool_,
    "STRING": jnp.float64,  # numeric-context cast; real string cast is host-side
    "TIMESTAMP": jnp.int64,
}


def _eval_filter(node: ir.FilterNode, arrays, params, n: int):
    if isinstance(node, ir.FConst):
        return jnp.full((n,), node.value, dtype=bool)
    if isinstance(node, ir.Interval):
        v = _eval_value(node.vexpr, arrays, params)
        mask = jnp.ones(v.shape, dtype=bool)
        if node.lo_param is not None:
            lo = params[node.lo_param]
            mask &= (v >= lo) if node.lo_inclusive else (v > lo)
        if node.hi_param is not None:
            hi = params[node.hi_param]
            mask &= (v <= hi) if node.hi_inclusive else (v < hi)
        if mask.ndim == 2:  # MV plane: row matches if any value matches
            mask = mask.any(axis=1)
        return mask
    if isinstance(node, ir.Lut):
        m = _lut_mask(params[node.lut_param], arrays[node.ids_slot])
        if m.ndim == 2:
            m = m.any(axis=1)
        return m
    if isinstance(node, ir.Isin):
        v = _eval_value(node.vexpr, arrays, params)
        vals = params[node.values_param]
        return (v[:, None] == vals[None, :]).any(axis=1)
    if isinstance(node, ir.Null):
        return arrays[node.null_slot]
    if isinstance(node, ir.MaskParam):
        return params[node.idx]
    if isinstance(node, ir.FAnd):
        m = _eval_filter(node.children[0], arrays, params, n)
        for c in node.children[1:]:
            m &= _eval_filter(c, arrays, params, n)
        return m
    if isinstance(node, ir.FOr):
        m = _eval_filter(node.children[0], arrays, params, n)
        for c in node.children[1:]:
            m |= _eval_filter(c, arrays, params, n)
        return m
    if isinstance(node, ir.FNot):
        return ~_eval_filter(node.child, arrays, params, n)
    raise TypeError(f"unknown filter node {node}")


def _apply_packed(arrays: tuple, packed: tuple) -> tuple:
    """Widen narrow (uint8/uint16) id planes to int32 in-register. A
    sub-byte bitstream decode was tried and measured ~1000x slower on TPU
    than this astype (the 32-lane stack/reshape forces lane relayouts), so
    byte-aligned narrow planes are the TPU-correct HBM packing — 4x/2x less
    residency and read bandwidth, decode fused for free. `packed` entries
    are (slot, width) with width ∈ {8, 16} (see dict_ids_packed)."""
    if not packed:
        return arrays
    out = list(arrays)
    with jax.named_scope("widen"):
        for slot, _width in packed:
            out[slot] = out[slot].astype(jnp.int32)
    return tuple(out)


# ---------------------------------------------------------------------------
# Stable names on the device
# ---------------------------------------------------------------------------

_NAMED_JITS: dict = {}


def jit_named(fn, name: str, **jit_kwargs):
    """`jax.jit(fn)` under the name ``name``: the XLA module, and with it
    every event of the program in a profiler trace, reads `jit_<name>`
    (XLA appends its own fingerprint). One jitted callable per (fn, name);
    its executables are keyed by the static arguments as ever, so a
    compiled family stays one executable."""
    key = (fn, name)
    jitted = _NAMED_JITS.get(key)
    if jitted is None:
        if len(_NAMED_JITS) >= 4096:  # the compile-cache guard's bound
            _NAMED_JITS.clear()

        @wraps(fn)  # keeps the signature static_argnames resolve against
        def named(*args, **kwargs):
            return fn(*args, **kwargs)

        named.__name__ = named.__qualname__ = name
        jitted = _NAMED_JITS.setdefault(key, jax.jit(named, **jit_kwargs))
    return jitted


class ProgramJit:
    """A jitted callable of (program, ...) whose XLA module is named for
    the program: `jit_<prefix>_<ir.program_label(program)>` where a plain
    `jax.jit` would name every query shape after the one Python function.
    Calls and `.lower()` go to the label's own `jax.jit`."""

    def __init__(self, fn, prefix: str, static_argnames: tuple):
        self._fn, self._prefix = fn, prefix
        self._static = static_argnames
        self.__name__ = fn.__name__
        self.__doc__ = fn.__doc__

    def _jit(self, program):
        return jit_named(self._fn,
                         f"{self._prefix}_{ir.program_label(program)}",
                         static_argnames=self._static)

    def __call__(self, program, *args, **kwargs):
        return self._jit(program)(program, *args, **kwargs)

    def lower(self, program, *args, **kwargs):
        return self._jit(program).lower(program, *args, **kwargs)


class PackedOuts:
    """Kernel outputs flattened into ONE device buffer + host-side metas.

    Every materialized array is a device→host fetch of its own, so a query
    with k outputs would cost k fetches. Packing on device makes the whole
    query ONE D2H transfer; shapes/dtypes are host-known attributes of the
    device arrays, so unpacking never touches the device."""

    __slots__ = ("flat", "metas")

    def __init__(self, flat, metas):
        self.flat = flat
        self.metas = metas  # [(np.dtype, shape), ...]


# f64 outputs are encoded with pure arithmetic: the TPU compiler's x64
# rewrite has no f64 bitcast-convert (UNIMPLEMENTED on libtpu 0.0.34,
# tests/test_tpu_compile.py pins the pack). Scale by a power-of-two
# bucket into f32-safe exponent range, split into a non-overlapping f32
# triplet (a = f32(y), b = f32(y-a), c = f32(y-a-b) — exact: 3x24 bits
# cover the 53-bit mantissa with every residual in f32 normal range), and
# carry bucket + nan/inf/sign flags in a fourth u32 word. Bit-exact for
# every f64 including subnormals, +-0, +-inf, nan.
_F64_HALF_SCALES = tuple(2.0 ** (-90 * k) for k in range(-6, 7))


def _encode_f64(x):
    finite = jnp.isfinite(x)
    xs = jnp.where(finite, x, 0.0)
    ax = jnp.abs(xs)
    # bucket k: exponent(x) in [180k-60, 180k+120) — thresholds 2^(180k-60)
    # for k=-5..6 (the k=-6 threshold underflows f64 and is implicit)
    k = sum(((ax >= (2.0 ** (180 * kk - 60))).astype(jnp.int32))
            for kk in range(-5, 7)) - 6
    half = jnp.asarray(_F64_HALF_SCALES, dtype=jnp.float64)[k + 6]
    y = xs * half * half  # two exact multiplies (2^(180*6) overflows alone)
    a = y.astype(jnp.float32)
    r1 = y - a.astype(jnp.float64)
    b = r1.astype(jnp.float32)
    c = (r1 - b.astype(jnp.float64)).astype(jnp.float32)
    # signbit without bitcast (jnp.signbit bitcasts f64 internally, which
    # this encoding avoids): 1/-0.0 = -inf distinguishes the zero sign
    neg = (x < 0) | ((x == 0) & (jnp.float64(1.0) / x < 0))
    meta = ((k + 6).astype(jnp.uint32)
            | (jnp.isnan(x).astype(jnp.uint32) << 8)
            | ((~finite & ~jnp.isnan(x)).astype(jnp.uint32) << 9)
            | (neg.astype(jnp.uint32) << 10))
    words = jnp.stack(
        [jax.lax.bitcast_convert_type(a, jnp.uint32),
         jax.lax.bitcast_convert_type(b, jnp.uint32),
         jax.lax.bitcast_convert_type(c, jnp.uint32), meta], axis=-1)
    return words


def _decode_f64(raw: np.ndarray, shape) -> np.ndarray:
    w = raw.view(np.uint32).reshape(-1, 4)
    a = np.ascontiguousarray(w[:, 0]).view(np.float32).astype(np.float64)
    b = np.ascontiguousarray(w[:, 1]).view(np.float32).astype(np.float64)
    c = np.ascontiguousarray(w[:, 2]).view(np.float32).astype(np.float64)
    k = (w[:, 3] & 0xFF).astype(np.int32) - 6
    neg = (w[:, 3] >> 10) & 1
    x = np.ldexp(a + b + c, 180 * k)
    zneg = (x == 0) & (neg == 1)  # -0.0 + 0.0 = +0.0 loses the zero sign
    if zneg.any():
        x = np.where(zneg, -0.0, x)
    isinf = (w[:, 3] >> 9) & 1
    if isinf.any():
        x = np.where(isinf == 1, np.where(neg == 1, -np.inf, np.inf), x)
    isnan = (w[:, 3] >> 8) & 1
    if isnan.any():
        x = np.where(isnan == 1, np.nan, x)
    return x.reshape(shape)


def _word_planes(o) -> list:
    """`o` as flat uint32 planes. A 4-byte dtype is one plane; wider ones
    split into one plane per 4-byte word of a value (2 for 64-bit ints, 4
    for the f64 wire format). A narrower dtype (selection bitmaps, bool
    planes) packs 2 or 4 values per word, again planar: with m words, word
    j holds values j, m+j, 2m+j, ... lowest bits first — contiguous slices
    only. Words are never interleaved on device: a (.., 2)/(.., 4) minor
    dim flattened, or a 64-bit bitcast-convert, costs the chip's compiler
    tens of seconds per program; `_value_major_chunks` transposes on the
    host instead."""
    if o.dtype == jnp.float64:
        w = _encode_f64(o)
        return [w[..., k].reshape(-1) for k in range(4)]
    if o.dtype.itemsize == 8:
        return [(o & 0xFFFFFFFF).astype(jnp.uint32).reshape(-1),
                (o >> 32).astype(jnp.uint32).reshape(-1)]
    if o.dtype.itemsize == 4:
        return [jax.lax.bitcast_convert_type(o, jnp.uint32).reshape(-1)]
    if o.dtype == jnp.bool_:
        o = o.astype(jnp.uint8)
    bits = 8 * o.dtype.itemsize
    per = 32 // bits
    u = jax.lax.bitcast_convert_type(
        o, jnp.uint8 if bits == 8 else jnp.uint16)
    u = u.astype(jnp.uint32).reshape(-1)
    m = -(-u.shape[0] // per)
    u = jnp.concatenate([u, jnp.zeros(per * m - u.shape[0], jnp.uint32)])
    w = u[:m]
    for k in range(1, per):
        w = w | (u[k * m:(k + 1) * m] << (bits * k))
    return [w]


def _pack_flat_impl(outs: tuple):
    """Concatenate the outputs into one flat uint32 device buffer, PLANAR:
    each output contributes its word planes back to back (all low words,
    then all high words, ...). Interleaving words on device — the
    value-major byte stream — is what the chip's compiler cannot do in
    usable time (`_word_planes`), so the host transposes instead."""
    with jax.named_scope("pack"):
        chunks = [w for o in outs for w in _word_planes(o)]
        return jnp.concatenate(chunks) if len(chunks) > 1 else chunks[0]


_pack_flat = jit_named(_pack_flat_impl, "_pack_flat")


def pack_outputs(outs: tuple, label: str = "") -> PackedOuts:
    """``label`` (ir.program_label of the program that made ``outs``) names
    the pack's XLA module `jit_pack_<label>`; without one it is
    `jit__pack_flat`."""
    metas = [(np.dtype(str(o.dtype)), tuple(o.shape)) for o in outs]
    pack = jit_named(_pack_flat_impl, f"pack_{label}") if label \
        else _pack_flat
    return PackedOuts(pack(outs), metas)


# lifetime count of device→host materializations at the packed-output
# fetch sites (single-stage packed outputs + the MSE fused-join group
# table); the perf guards pin a warm query to exactly ONE per dispatch
_HOST_FETCHES = [0]
# a traced request's own fetches and bytes (engine/executor.device_fetch)
_FETCH_TLS = threading.local()


def host_fetches() -> int:
    """Process-lifetime device→host fetch count (packed-output sites)."""
    return _HOST_FETCHES[0]


def count_host_fetch(nbytes: int = 0) -> None:
    """Record one deliberate device→host crossing. Every fetch site in the
    engine calls this right before its np.asarray so the structure guards
    can pin 'exactly one crossing per stage' without monkeypatching jax."""
    _HOST_FETCHES[0] += 1
    tally = getattr(_FETCH_TLS, "tally", None)
    if tally is not None:
        tally[0] += 1
        tally[1] += nbytes


@contextmanager
def fetch_tally():
    """[fetches, bytes] of the crossings this thread counts inside."""
    outer = getattr(_FETCH_TLS, "tally", None)
    tally = _FETCH_TLS.tally = [0, 0]
    try:
        yield tally
    finally:
        _FETCH_TLS.tally = outer


def unpack_outputs(p: PackedOuts) -> list:
    """One packed buffer as host arrays: the query's single device→host
    transfer, then its decode (`fetch_packed_batch` of one)."""
    return fetch_packed_batch([p])[0]


def _value_major_chunks(flat: np.ndarray, metas, planar: bool):
    """Yield (dtype, shape, bytes-of-that-output) over packed bytes in
    VALUE-MAJOR order — the layout the PTDP wire format and `_decode_f64`
    are defined over. `planar` says the bytes are a `_pack_flat` buffer,
    whose word planes (`_word_planes`) are transposed back here — the one
    place on the host that knows the device layout. Otherwise they already
    are the value-major stream (a PTDP payload)."""
    off = 0
    for dt, shape in metas:
        count = int(np.prod(shape, dtype=np.int64))
        words = 4 if dt == np.float64 else max(1, dt.itemsize // 4)
        nbytes = count * (4 * words if dt.itemsize >= 4 else dt.itemsize)
        stored = -(-nbytes // 4) * 4 if planar else nbytes
        chunk = flat[off:off + stored]
        off += stored
        if planar and dt.itemsize < 4:
            per = 4 // dt.itemsize
            chunk = np.ascontiguousarray(
                chunk.view(f"u{dt.itemsize}").reshape(-1, per).T
            ).reshape(-1)[:count].view(np.uint8)
        elif planar and words > 1:
            chunk = np.ascontiguousarray(
                chunk.view(np.uint32).reshape(words, count).T
            ).reshape(-1).view(np.uint8)
        yield dt, shape, chunk


def _split_flat(flat: np.ndarray, metas, planar: bool = True) -> list:
    """Packed bytes (uint8 view) → one array per output."""
    return [_decode_f64(chunk, shape) if dt == np.float64
            else chunk.view(dt).reshape(shape)
            for dt, shape, chunk in _value_major_chunks(flat, metas, planar)]


def canonical_bytes(flat: np.ndarray, metas) -> bytes:
    """The value-major byte stream of a fetched `_pack_flat` buffer
    (cluster/datatable.py's PTDP payload)."""
    return b"".join(chunk.tobytes() for _dt, _shape, chunk in
                    _value_major_chunks(flat.view(np.uint8), metas, True))


@jax.jit
def _concat_flats(flats: tuple):
    return jnp.concatenate(flats)


# batch-fetch only latency-DOMINATED transfers: above this total the
# copy time dwarfs the per-fetch latency, and the on-device concat copy +
# whole-batch host buffer would only raise peak memory for no win
_BATCH_FETCH_CAP = 128 << 20


def fetch_packed_batch(packs: list) -> list:
    """Materialize many segments' packed outputs in as few device→host
    transfers as possible: EQUAL-LENGTH flat buffers (same segment bucket ×
    same program — the multi-segment combine case) concatenate on device
    and fetch once, so a 16-segment combine costs one fetch instead of
    16. Unequal lengths fetch individually — batching them
    would compile a fresh concat executable per length combination.

    Every buffer is copied first, then all are decoded: under the caller's
    DEVICE_FETCH, one DEVICE_COPY span holds the wait for the results and
    the copies, one FETCH_DECODE span the host's transposes and slicing."""
    by_len: dict[int, list[int]] = {}
    for i, p in enumerate(packs):
        by_len.setdefault(int(p.flat.shape[0]), []).append(i)
    fetched = []  # (member positions, bytes a member, their host buffer)
    with TRACING.scope(DEVICE_COPY):
        for n, idxs in by_len.items():
            nbytes = n * 4
            if len(idxs) > 1 and nbytes * len(idxs) <= _BATCH_FETCH_CAP:
                count_host_fetch(nbytes * len(idxs))
                fetched.append((idxs, nbytes, np.asarray(_concat_flats(
                    tuple(packs[i].flat for i in idxs)))))
                continue
            for i in idxs:
                count_host_fetch(nbytes)
                fetched.append(([i], nbytes, np.asarray(packs[i].flat)))
    out = [None] * len(packs)
    with TRACING.scope(FETCH_DECODE):
        for idxs, nbytes, flat in fetched:
            flat = flat.view(np.uint8)
            for j, i in enumerate(idxs):
                out[i] = _split_flat(flat[j * nbytes:(j + 1) * nbytes],
                                     packs[i].metas)
    return out


def _run_program(program: ir.Program, arrays: tuple, params: tuple, num_docs, padded: int,
                 row_offset=0, packed: tuple = (), fused: str = "",
                 fused_lut_meta: tuple = ()):
    """Execute a Program over padded column planes. Returns a tuple:

    selection   → (mask bitmap, packed little-endian)
    aggregation → (count, agg_0, agg_1, ...) each shape (1+trash,) sliced later
    group_by    → (counts[G+1], agg_0[G+1], ...)

    `padded` is the bucket row count (static); every SV plane has that length.
    `row_offset` supports row-sharded multi-device execution (shard_map over a
    mesh row axis — parallel/mesh.py): each shard sees rows
    [row_offset, row_offset+padded) of the global segment.
    `packed` marks id slots resident in HBM as packed/narrow planes.
    `fused` ('' | 'tpu' | 'interpret') enables the single-pass fused dense
    group-by kernel (ops/fused_groupby.py) for programs in its scope — the
    RAW narrow planes feed the kernel directly, skipping `_apply_packed`.
    """
    if fused and program.mode == "group_by":
        from . import fused_groupby

        fp = fused_groupby.plan(program, arrays, fused_lut_meta)
        if fp is not None:
            # filter and group-by in ONE Pallas kernel (its own `name=`)
            with jax.named_scope("group_by_dense"):
                return fused_groupby.execute(
                    fp, program, arrays, params, num_docs, padded,
                    row_offset, interpret=(fused == "interpret"))
    arrays = _apply_packed(arrays, packed)
    return _run_program_impl(program, arrays, params, num_docs, padded, row_offset)


# the XLA module of a program is `jit_scan_<label>`, solo, batched or
# sharded (parallel/mesh.py) alike
run_program = ProgramJit(
    _run_program, "scan",
    ("program", "padded", "packed", "fused", "fused_lut_meta"))


def _run_program_batch(program: ir.Program, arrays: tuple, params: tuple,
                       num_docs, padded: int, packed: tuple = ()):
    """Execute one Program over a stacked FAMILY of segments in a single
    dispatch: every plane in `arrays` and every param in `params` carries a
    leading batch dim [S, ...] (one row per member segment) and `num_docs`
    is an (S,) vector. The body is `jax.vmap` (`lax.map` for the sort-based
    group-by) of the exact per-segment
    implementation, so each output gains a leading S dim and row s is
    bit-for-bit what `run_program(..., fused="")` would have produced for
    member s — the host slices outputs per segment after one transfer.

    Narrow packed planes widen via `_apply_packed` BEFORE the vmap
    (elementwise astype is shape-agnostic), so stacks stay narrow in HBM.
    The fused dense kernel is per-segment-only: batched families always
    take the reference `_run_program_impl` path.
    """
    arrays = _apply_packed(arrays, packed)

    if program.mode == "group_by_sparse":
        # the sort-based kernel runs member after member: a sort batched
        # over 16 x 4,194,304 rows took the chip 1.8 times as long as 16
        # sorts of one member each (PR 30: 572 and 600 ms a family against
        # 314 and 374), and a member's `lax.cond` (the short tail of
        # `_run_sparse_group_by`) stays a branch here where vmap would
        # run both sides. The filter is batched like every family's; the
        # scope is opened AROUND the loop because a trace files an op under
        # the first scope of its name (`while/body/...` otherwise), and the
        # profiler gives the loop's own op no name, so nothing counts twice
        masks = jax.vmap(
            lambda arrays_s, params_s, nd: _filter_mask(
                program, arrays_s, params_s, nd, padded))(
                    arrays, params, num_docs)
        with jax.named_scope("group_by_sparse"):
            return jax.lax.map(
                lambda member: _run_masked(program, *member, padded),
                (arrays, params, masks))

    def one(arrays_s, params_s, nd):
        return _run_program_impl(program, arrays_s, params_s, nd, padded)

    return jax.vmap(one)(arrays, params, num_docs)


run_program_batch = ProgramJit(_run_program_batch, "scan",
                               ("program", "padded", "packed"))


def _run_program_impl(program: ir.Program, arrays: tuple, params: tuple, num_docs, padded: int,
                      row_offset=0):
    mask = _filter_mask(program, arrays, params, num_docs, padded, row_offset)
    return _run_masked(program, arrays, params, mask, padded)


def _filter_mask(program: ir.Program, arrays: tuple, params: tuple, num_docs,
                 padded: int, row_offset=0):
    """(padded,) bool: the rows below `num_docs` that pass the filter."""
    # the scopes name the program's parts in a profiler trace (an XLA
    # fusion is filed under the scope of its root instruction)
    with jax.named_scope("filter"):
        mask = (jnp.arange(padded, dtype=jnp.int32) + row_offset) < num_docs
        if program.filter is not None:
            mask &= _eval_filter(program.filter, arrays, params, padded)
    return mask


def _run_masked(program: ir.Program, arrays: tuple, params: tuple, mask,
                padded: int):
    """The program's part after the filter, over the rows of `mask`."""
    n = padded
    if program.mode == "selection":
        # ship the mask as a BITMAP (n/8 uint8), not one byte per row: a
        # 100M-row segment's selection leaf costs 12.5MB D2H instead of
        # 100MB.
        # Padded buckets (and row shards of them) are always 8-divisible.
        # bitorder matches every other packed bitmap in the repo
        # (segment/bitpack.py, aggregation.py occupancy words: little).
        with jax.named_scope("select"):
            return (jnp.packbits(mask, bitorder="little"),)

    if program.mv_group_slot is not None and program.mode in (
            "group_by", "group_by_sparse"):
        # MV group dim: expand to (doc × mv-slot) pairs — broadcast every
        # 1-D plane across the MV width, flatten the MV id matrix, mask
        # off pad slots — and let the dense/sparse paths run unchanged.
        # Matched DOCS are counted pre-expansion (pair counts ≠ docs).
        scanned_docs = mask.astype(jnp.int32).sum().astype(jnp.int64)[None]
        mv = arrays[program.mv_group_slot]  # (n, max_mv) int32
        width = mv.shape[1]
        doc_slots = set(program.mv_doc_slots)
        arrays = tuple(
            mv.reshape(-1) if i == program.mv_group_slot
            else (jnp.broadcast_to(a[:, None], (n, width)).reshape(-1)
                  if i in doc_slots else a)  # dict planes / filter-only MV
            for i, a in enumerate(arrays))  # matrices pass through
        mask = (mask[:, None] & (mv != program.mv_group_card)).reshape(-1)
        n = n * width
        if program.mode == "group_by_sparse":
            with jax.named_scope("group_by_sparse"):
                outs = _run_sparse_group_by(program, arrays, params, mask, n)
        else:
            with jax.named_scope("group_by_dense"):
                outs = _dense_group_by_entry(program, arrays, params, mask, n)
        return outs + (scanned_docs,)

    if program.mode == "group_by_sparse":
        with jax.named_scope("group_by_sparse"):
            return _run_sparse_group_by(program, arrays, params, mask, n)

    if program.mode != "group_by":
        # un-grouped aggregation: NO scatter at all — plain masked
        # reductions shaped (value, trash) to keep the output contract.
        # Scatters to a 2-slot table were pure overhead (and 64-bit
        # scatters are emulated on TPU)
        with jax.named_scope("aggregate"):
            return _run_ungrouped(program, arrays, params, mask, n)
    with jax.named_scope("group_by_dense"):
        return _dense_group_by_entry(program, arrays, params, mask, n)


def _dense_group_by_entry(program: ir.Program, arrays, params, mask, n):
    """Dense group-by gid assembly + dispatch, shared by the SV path and
    the MV (doc × mv-slot) pre-expanded path — after expansion the MV
    dim's flattened ids are just another id plane; pad slots are already
    masked → trash."""
    gid = jnp.zeros((n,), dtype=jnp.int32)
    if program.group_vexprs:
        for vexpr, stride in zip(program.group_vexprs, program.group_strides):
            v = _eval_value(vexpr, arrays, params)
            gid = gid + v.astype(jnp.int32) * jnp.int32(stride)
    else:
        for slot, stride in zip(program.group_slots, program.group_strides):
            gid = gid + arrays[slot].astype(jnp.int32) * jnp.int32(stride)
    trash = jnp.int32(program.num_groups)
    gid = jnp.where(mask, gid, trash)
    return _run_dense_group_by(program, arrays, params, mask, gid,
                               program.num_groups + 1, n)


def _run_dense_group_by(program: ir.Program, arrays, params, mask, gid,
                        num_segments, n):
    """COUNT and every integer SUM with room ride ONE MXU pass (7-bit limb
    planes through the kron-factored one-hot matmul — ops/mxu_groupby.py;
    `dense_sum_limbs` says which); a MIN or MAX over a few groups is a
    masked reduction (`min_max_form`); scatters only remain for what
    neither can reduce (min/max of a larger table, float sums, matrix ops).
    Replaces the batched (n, C) vector-payload scatter, whose minor dim was
    padded 6→128 lanes by TPU tiling (a 21x HBM blowup that OOMed real
    100M-row segments)."""
    planes = [mask.astype(mxu_groupby.PLANE_DTYPE)]  # count plane
    values = {}

    def value(agg):
        if agg not in values:
            values[agg] = _eval_value(agg.vexpr, arrays, params)
        return values[agg]

    limbs, n_planes = dense_sum_limbs(
        program, n, lambda agg: value(agg).dtype == jnp.int32)
    recipes: list = []  # per agg: callable(sums, counts) | None → _run_agg
    for agg, lb in zip(program.aggs, limbs):
        if agg.kind == "count":
            recipes.append(lambda sums, counts: counts)
        elif lb is None:
            recipes.append(None)
        else:
            recipes.append(_limb_recipe(agg, lb, value, arrays, params,
                                        mask, planes))
    if n_planes:
        sums = mxu_groupby.limb_sums(planes, gid, num_segments)
        counts = sums[0]
    else:
        # too many groups/planes for the VMEM-resident accumulator: sums
        # drop back to scatters; COUNTs still answer from the shared
        # counts column (their recipe reads no limb sums)
        sums = None
        counts = jax.ops.segment_sum(
            mask.astype(jnp.int32), gid,
            num_segments=num_segments).astype(jnp.int64)
    outputs = [counts]
    for agg, recipe in zip(program.aggs, recipes):
        if recipe is None:
            outputs.append(_run_agg(agg, arrays, params, mask, gid,
                                    num_segments, n, counts=counts))
        else:
            outputs.append(recipe(sums, counts))
    return tuple(outputs)


def _limb_recipe(agg: ir.AggOp, limbs, value, arrays, params, mask,
                 planes):
    """Append a SUM's digit planes (`sum_limbs`' layout) to `planes`;
    returns its recipe (sums, counts) → output column, the exact int64
    total: a value that fits int32 is split from its 32-bit plane, a wider
    one from its two 32-bit words (`_value_words`: a product of two int32
    factors never becomes an emulated s64 multiply). The total leaves the
    device as an integer: the TPU carries a float64 in two float32 words
    (`X64Combine`), exact to about 2^48 only (PR 36), so the host makes
    the float a SUM answers in."""
    shifts, neg_bit, wide = limbs
    b = mxu_groupby.LIMB_BITS
    refs = []
    if wide:
        lo, hi = _value_words(agg.vexpr, arrays, params)
        zero = jnp.uint32(0)
        lo, hi = jnp.where(mask, lo, zero), jnp.where(mask, hi, zero)
        # a negative value's bits from `neg_bit` up copy its sign: the
        # top digit stops there, where the negative-count plane weighs
        top = 64 if neg_bit is None else neg_bit
        for s in shifts:
            refs.append((len(planes), s))
            planes.append(_digit(lo, hi, s, min(b, top - s))
                          .astype(mxu_groupby.PLANE_DTYPE))
        negative = hi >> 31
    else:
        v = _sum_value32(agg, value(agg), arrays, params)
        vm = jnp.where(mask, v, 0).astype(jnp.int32)
        u = vm.astype(jnp.uint32)
        for s in shifts:
            refs.append((len(planes), s))
            planes.append(((u >> s) & jnp.uint32((1 << b) - 1))
                          .astype(mxu_groupby.PLANE_DTYPE))
        negative = vm < 0
    neg_ref = None
    if neg_bit is not None:
        neg_ref = len(planes)
        planes.append(negative.astype(mxu_groupby.PLANE_DTYPE))

    def recipe(sums, counts, _refs=refs, _neg=neg_ref):
        total = jnp.zeros(counts.shape[0], dtype=jnp.int64)
        for idx, shift in _refs:
            total = total + (sums[idx] << shift)
        if _neg is not None:
            total = total - (sums[_neg] << neg_bit)
        return total

    return recipe


def _run_ungrouped(program: ir.Program, arrays, params, mask, n):
    count = mask.astype(jnp.int32).sum().astype(jnp.int64)
    zero_i = jnp.int64(0)
    outputs = [jnp.stack([count, zero_i])]
    for agg in program.aggs:
        if agg.kind == "count":
            outputs.append(jnp.stack([count, zero_i]))
            continue
        if agg.kind in ("distinct_bitmap", "value_hist", "hist_fixed",
                        "hist_adaptive"):
            # matrix shapes keep the (1 group + trash) scatter layout
            outputs.append(_run_agg(agg, arrays, params, mask,
                                    jnp.where(mask, 0, 1).astype(jnp.int32),
                                    2, n, counts=None))
            continue
        v = _eval_value(agg.vexpr, arrays, params)
        is_int = jnp.issubdtype(v.dtype, jnp.integer)
        fast32 = is_int and _fits_i32(v, agg)
        if agg.kind == "sum":
            if fast32 and n % 4096 == 0:
                v = _sum_value32(agg, v, arrays, params)
                # the TPU has no 64-bit ALU: a whole-column i64 (or f64)
                # reduction runs on emulated adds per element. Split into
                # u16 limbs, reduce 4096-element blocks in NATIVE i32
                # (4096*65535 < 2^31: exact), and only the tiny per-block
                # partials touch i64. Two's complement fixes negatives:
                # sum(u32) = sum(v) + 2^32 * count_neg. _limb_shifts skips
                # the high limb and/or the negative pass when the planner
                # proved bounds.
                vm = jnp.where(mask, v.astype(jnp.int32), 0)
                u = vm.astype(jnp.uint32)
                shifts, nonneg = _limb_shifts(agg.vmin, agg.vmax, 16)

                def _blk(x):
                    return x.reshape(-1, 4096).sum(
                        axis=1).astype(jnp.int64).sum()

                s = jnp.int64(0)
                for sh in shifts:
                    s = s + (_blk(((u >> sh) & jnp.uint32(0xFFFF))
                                  .astype(jnp.int32)) << sh)
                if not nonneg:
                    s = s - (_blk((vm < 0).astype(jnp.int32)) << 32)
            elif is_int:
                # the int64 total itself, as the 32-bit path above
                # answers: the chip's float64 is exact to about 2^48
                # (PR 36), so the host makes the float a SUM answers in
                s = jnp.where(mask, v, 0).astype(jnp.int64).sum()
            else:
                s = jnp.where(mask, v, 0).astype(jnp.float64).sum()
            zero = jnp.int64(0) if s.dtype == jnp.int64 else jnp.float64(0)
            outputs.append(jnp.stack([s, zero]))
        elif agg.kind == "sumsq":
            vf = jnp.where(mask, v, 0).astype(jnp.float64)
            outputs.append(jnp.stack([(vf * vf).sum(), jnp.float64(0)]))
        elif agg.kind == "min":
            if fast32:  # native i32 compares; empty → +inf via the count
                s = jnp.where(mask, v.astype(jnp.int32), _I32_MAX).min()
                out = jnp.where(count > 0, s.astype(jnp.float64), jnp.inf)
            elif v.dtype == jnp.float32:  # exact: f32→f64 is lossless
                out = jnp.where(mask, v, jnp.float32(jnp.inf)).min() \
                    .astype(jnp.float64)
            else:
                vf = jnp.where(mask, v, jnp.inf).astype(jnp.float64)
                out = vf.min()
            outputs.append(jnp.stack([out, jnp.float64(jnp.inf)]))
        elif agg.kind == "max":
            if fast32:
                s = jnp.where(mask, v.astype(jnp.int32), _I32_MIN).max()
                out = jnp.where(count > 0, s.astype(jnp.float64), -jnp.inf)
            elif v.dtype == jnp.float32:
                out = jnp.where(mask, v, jnp.float32(-jnp.inf)).max() \
                    .astype(jnp.float64)
            else:
                vf = jnp.where(mask, v, -jnp.inf).astype(jnp.float64)
                out = vf.max()
            outputs.append(jnp.stack([out, jnp.float64(-jnp.inf)]))
        else:
            raise ValueError(f"unknown agg kind {agg.kind}")
    return tuple(outputs)


def _run_sparse_group_by(program: ir.Program, arrays, params, mask, n):
    """High-cardinality group-by: sort-based aggregation on device.

    When the cardinality product exceeds the dense segment_sum table limit,
    the reference switches DictionaryBasedGroupKeyGenerator to hash maps
    with a numGroupsLimit trim (DictionaryBasedGroupKeyGenerator.java:119-137,
    InstancePlanMakerImplV2.java:245-270). Hash maps are hostile to the TPU's
    vector units, but a bitonic sort of 64-bit composite keys is not:

        key   = Σ dict_ids[d] * stride[d]          (int64; masked → sentinel)
        sort  (key, agg inputs...) together        (lax.sort, one fused pass)
        first = key[i] != key[i-1]                 (segment boundaries)
        scans = prefix sums / segmented scans      (running totals per group)
        last  = key[i] != key[i+1]                 (a group's totals stand here)
        out_k = the rows flagged `last`, moved to the first K slots by a
                second sort keyed by the row index (`_GroupEnds`)

    Groups past numGroupsLimit (in key sort order) route to the trash slot —
    the same "stop creating new groups" trim semantics as the reference. The
    composite keys of the surviving groups are emitted as the LAST output so
    the host can decode per-dim dict ids with the usual stride arithmetic.

    Two fast paths shave the sort cost (ir.sparse_groupby_path names the
    variant for EXPLAIN IMPLEMENTATION):
    - keys_presorted: the single group key plane is already nondecreasing in
      doc order (sorted ingestion) — the rows are not sorted by key; group
      edges come from transitions in the raw id plane.
    - sort-iota + gather: with >= 2 payload operands, sort only
      (key[, distinct_ids], iota32) and gather each payload through the
      permutation — (1+A)·n sorted bytes become ~2·n.
    A key-ordered trim of k slots that k rows or fewer pass sorts only those
    rows, gathered first (`_passing_rows`; decided per member at run time).
    """
    # 64-bit sorts/scatters are emulated on TPU: sort 32-bit keys whenever
    # the composite key space fits (key_space is static on the Program)
    key32 = 0 < program.key_space < (1 << 31) - 1
    kdtype = jnp.int32 if key32 else jnp.int64
    key = jnp.zeros((n,), dtype=kdtype)
    if program.group_vexprs:
        for vexpr, stride in zip(program.group_vexprs, program.group_strides):
            key = key + _eval_value(vexpr, arrays, params).astype(kdtype) * stride
    else:
        for slot, stride in zip(program.group_slots, program.group_strides):
            key = key + arrays[slot].astype(kdtype) * stride
    sentinel = (jnp.int32((1 << 31) - 1) if key32
                else jnp.int64(ir.SPARSE_KEY_SPACE))
    if not program.keys_presorted:
        # masked rows sort to a sentinel tail. The presorted path keeps the
        # RAW key plane instead: rows never move, so masked rows stay in
        # place and are skipped via op identities + mask prefix sums.
        key = jnp.where(mask, key, sentinel)

    # agg inputs with mask-neutral elements, computed BEFORE the sort so one
    # lax.sort carries key + all values into group-contiguous order.
    # COUNT DISTINCT rides the SAME sort as a SECONDARY key: with dict ids
    # sorted within each group, distinct (group, id) pairs are exactly the
    # first-occurrence rows, and per-slot distinct counts + id bitmaps
    # reduce on the already-computed group edges — no second n-length
    # sort, no n-length output (the old pair-list output was ~100x the
    # query's real bytes and blew up compiles).
    num_sort_keys = 1
    distinct_aggs = [a for a in program.aggs if a.kind == "distinct_bitmap"]
    if len(distinct_aggs) > 1:
        raise ValueError("sparse group-by supports one DISTINCT column")
    # DISTINCT ids PACK into the key's low digits when the combined space
    # fits int32 (key' = key*card + id): one sort operand fewer — the
    # secondary sort order arrives free, and uniq/group edges both fall
    # out of the single packed key. Falls back to a two-key sort when the
    # product overflows.
    pack_card = None
    if distinct_aggs and key32 and not program.keys_presorted and \
            0 < program.key_space * distinct_aggs[0].card < _I32_MAX:
        pack_card = int(distinct_aggs[0].card)
        ids_raw = arrays[distinct_aggs[0].ids_slot].astype(jnp.int32)
        key = jnp.where(mask, key * jnp.int32(pack_card) + ids_raw, sentinel)
    operands = [key]
    if distinct_aggs and pack_card is None:
        operands.append(arrays[distinct_aggs[0].ids_slot].astype(jnp.int32))
        num_sort_keys = 2
    specs = []  # per agg: (reduce_kind, operand index | None[, agg])
    for agg in program.aggs:
        if agg.kind == "count":
            specs.append(("count", None))
            continue
        if agg.kind == "distinct_bitmap":
            specs.append(("distinct", None if pack_card else 1, agg))
            continue
        v = _eval_value(agg.vexpr, arrays, params)
        fast32 = jnp.issubdtype(v.dtype, jnp.integer) and _fits_i32(v, agg)
        if agg.kind in ("sum", "sumsq"):
            if agg.kind == "sumsq":
                v = jnp.where(mask, v, 0).astype(jnp.float64)
                v = v * v
                specs.append(("sum_f", len(operands), agg))
            elif fast32:
                v = jnp.where(mask, v, 0).astype(jnp.int32)
                specs.append(("sum_i", len(operands), agg))
            else:
                v = jnp.where(mask, v, 0).astype(jnp.float64)
                specs.append(("sum_f", len(operands), agg))
        elif agg.kind == "min":
            if fast32:
                v = jnp.where(mask, v.astype(jnp.int32), _I32_MAX)
                specs.append(("min_i", len(operands), agg))
            else:
                v = jnp.where(mask, v, jnp.inf).astype(jnp.float64)
                specs.append(("min_f", len(operands), agg))
        elif agg.kind == "max":
            if fast32:
                v = jnp.where(mask, v.astype(jnp.int32), _I32_MIN)
                specs.append(("max_i", len(operands), agg))
            else:
                v = jnp.where(mask, v, -jnp.inf).astype(jnp.float64)
                specs.append(("max_f", len(operands), agg))
        else:  # matrix-shaped aggs are planner-rejected in sparse mode
            raise ValueError(f"agg kind {agg.kind} unsupported in sparse group-by")
        operands.append(v)

    if program.keys_presorted:
        return _presorted_sparse_tail(program, operands, specs, mask, n)

    def by_key(ops):
        if len(ops) - num_sort_keys >= 2:
            # sort-iota + gather: dragging every payload through the
            # bitonic sort network costs (num_keys+A)·n sorted bytes and A
            # extra compare-network permute lanes. Sort only (keys...,
            # iota32) and gather each payload through the permutation
            # instead — the sort moves ~2·n values and the payloads cross
            # HBM once via gathers. lax.sort is stable, so the permutation
            # (iota as the tie-broken last operand) reproduces the
            # multi-operand sort bit-for-bit.
            iota = jnp.arange(ops[0].shape[0], dtype=jnp.int32)
            head = jax.lax.sort(tuple(ops[:num_sort_keys]) + (iota,),
                                num_keys=num_sort_keys)
            perm = head[num_sort_keys]
            return tuple(head[:num_sort_keys]) + tuple(
                op[perm] for op in ops[num_sort_keys:])
        return jax.lax.sort(tuple(ops), num_keys=num_sort_keys)

    def tail(sorted_ops):
        n = sorted_ops[0].shape[0]
        skey_raw = sorted_ops[0]
        valid = skey_raw < sentinel
        if pack_card is not None:
            # unpack: group key = high digits; the id low digit feeds the
            # distinct branch. Sentinel rows' quotient stays huge (> any real
            # key) so the sentinel-tail ordering survives the division.
            skey = skey_raw // jnp.int32(pack_card)
            packed_sids = skey_raw - skey * jnp.int32(pack_card)
        else:
            skey = skey_raw
            packed_sids = None
        differs = skey[1:] != skey[:-1]
        first = jnp.concatenate([jnp.ones((1,), dtype=bool), differs]) & valid
        # a group's LAST row carries its totals: valid rows sort to the front,
        # so a group ends where the next key differs (the sentinel tail's does)
        last = jnp.concatenate([differs, jnp.ones((1,), dtype=bool)]) & valid
        n_valid = valid.astype(jnp.int32).sum()
        rows = jnp.arange(1, n + 1, dtype=jnp.int32)  # valid rows up to here

        cols = _running_columns(specs, sorted_ops, first)
        table = _GroupEnds(last, skey, rows, cols, program.num_groups, n_valid)

        outputs = [table.counts]
        for spec, col in zip(specs, table.cols):
            kind, oi = spec[0], spec[1]
            agg = spec[2] if len(spec) > 2 else None
            if kind == "count":
                outputs.append(table.counts)
            elif kind == "distinct":
                card = agg.card
                if oi is None:  # ids packed into the sort key's low digit
                    sids = packed_sids
                    uniq = jnp.concatenate(
                        [jnp.ones((1,), dtype=bool),
                         skey_raw[1:] != skey_raw[:-1]]) & valid
                else:
                    sids = sorted_ops[oi]  # dict ids, sorted within each group
                    uniq = jnp.concatenate(
                        [jnp.ones((1,), dtype=bool),
                         differs | (sids[1:] != sids[:-1])]) & valid
                bit = sids.astype(jnp.uint32)
                words = []
                for w in range(-(-card // 32)):
                    # each (group, id) bit appears at most once (uniq-masked),
                    # so the per-group OR equals the per-group SUM — one
                    # wrapping uint32 cumsum + edge diffs (mod-2^32 prefix
                    # differences are exact because every group sum < 2^32),
                    # instead of a log2(n)-pass segmented scan
                    val = jnp.where(uniq & ((bit >> 5) == jnp.uint32(w)),
                                    jnp.uint32(1) << (bit & jnp.uint32(31)),
                                    jnp.uint32(0))
                    words.append(table.diff(table.at(_prefix_sum(val))))
                outputs.append(_bitmap_rows(words))
            elif col is None:
                # unbounded int64 columns: f64 prefix DIFFS would round (the
                # per-group result must stay exact) — keep the limb scatters
                k = program.num_groups
                gidx = _prefix_sum(first.astype(jnp.int32)) - 1
                gid = jnp.where(valid & (gidx < k), gidx, jnp.int32(k))
                outputs.append(_segment_sum_exact_i64(
                    sorted_ops[oi], gid, k + 1, n, agg.vmin, agg.vmax,
                    indices_are_sorted=True).astype(jnp.float64))
            else:
                outputs.append(table.output(kind, col))
        outputs.append(table.keys)
        return tuple(outputs)

    # valid rows sort to the front: where the filter keeps an eighth of the
    # rows or fewer, everything after the sort (scans, and the table's sort)
    # runs over the first eighth alone (a branch under `lax.map`, as a
    # batch family runs; both sides under a vmap)
    short = max(n // 8, 2048)  # whole blocks of `_sorted_prefix_f64`

    def whole(ops):
        sorted_ops = by_key(ops)
        if 2 * short > n:
            return tail(sorted_ops)
        return jax.lax.cond(
            (sorted_ops[0] < sentinel).sum() <= short,
            lambda ops: tail(tuple(o[:short] for o in ops)), tail,
            sorted_ops)

    k = program.num_groups
    if program.exact_trim and k <= short:
        # a key-ordered trim keeps a member's first k keys: where k rows or
        # fewer pass the filter they are every row of its groups, so they
        # are gathered into k rows and only those are sorted
        return jax.lax.cond(
            mask.sum() <= k,
            lambda ops: tail(by_key(_passing_rows(ops, mask, k, sentinel))),
            whole, tuple(operands))
    return whole(operands)


def _passing_rows(ops, mask, k: int, sentinel):
    """`ops` at the rows where `mask` holds, in row order, in k rows: the
    j-th passing row is the first whose running count of passing rows
    reaches j (a binary search of k counts, no scatter). The first operand
    is the sort key: past the passing rows it reads `sentinel`, so the
    rows there sort last and count for no group."""
    count = _prefix_sum(mask.astype(jnp.int32))
    at = jnp.searchsorted(count, jnp.arange(1, k + 1, dtype=jnp.int32),
                          method="scan_unrolled")
    at = jnp.minimum(at, mask.shape[0] - 1)
    live = jnp.arange(k) < count[-1]
    return (jnp.where(live, ops[0][at], sentinel),) + tuple(
        op[at] for op in ops[1:])


def _bitmap_rows(words):
    """(k+1, W) uint32 bitmap matrix from W per-slot word columns (k,);
    the trash row is empty."""
    matrix = jnp.stack(words, axis=1)
    return jnp.concatenate(
        [matrix, jnp.zeros((1, matrix.shape[1]), jnp.uint32)])


def _running_columns(specs, ops, first) -> list:
    """Per spec, the per-row column a group's slot value is read from at
    the group's last row (None: count, distinct, or an int sum that keeps
    its exact limb scatters): an inclusive prefix for exact int sums
    (read as a difference, `_GroupEnds.diff`), a segmented running
    reduction that restarts at `first` for the rest."""
    cols = []
    for spec in specs:
        kind, oi = spec[0], spec[1]
        agg = spec[2] if len(spec) > 2 else None
        if kind in ("count", "distinct") or (
                kind == "sum_i" and not _prefix_exact_gate(ops[oi], agg)):
            cols.append(None)
        elif kind == "sum_i":
            cols.append(_sorted_prefix_f64(ops[oi], agg))
        else:
            # f64 sums: a GLOBAL prefix-diff would round each group to
            # ulp(global running total); the segmented tree scan keeps
            # rounding local to the group, like the scatter it replaces
            op = {"sum": jnp.add, "min": jnp.minimum,
                  "max": jnp.maximum}[kind[:3]]
            cols.append(_segmented_scan(ops[oi], first, op))
    return cols


def _flagged_to_front(flag, cols, size: int):
    """[row indices, *cols] of the rows where `flag` is set, in row order,
    in the first slots of `size`-slot columns: ONE unstable sort keyed by
    the row index (unflagged rows at the int32 sentinel) that carries the
    columns. Slots past the last flagged row hold the sentinel index (and
    whatever sorted there, or zeros past the rows' own count)."""
    n = flag.shape[0]
    row = jnp.where(flag, jnp.arange(n, dtype=jnp.int32), _I32_MAX)
    out = jax.lax.sort((row,) + tuple(cols), num_keys=1, is_stable=False)
    if size <= n:
        return [o[:size] for o in out]
    return [jnp.concatenate(
        [o, jnp.full((size - n,), _I32_MAX if i == 0 else 0, o.dtype)])
        for i, o in enumerate(out)]


class _GroupEnds:
    """A sort-based group table read off the rows that END a group.

    Both sparse paths bring the rows of a group together (the sort, or the
    segment's own order) and run prefix sums and segmented scans over
    them, so a group's totals stand in the row that ends it. Those rows
    move to the table's first slots by ONE unstable sort of (row index |
    sentinel, key, running count, columns...): the sort network carries
    the columns, and no slot is gathered. (The binary search this
    replaces made log2(n) gathers a slot and one more per column: at a
    table of a million slots that is tens of millions of gathers a
    segment, each about 10 ns on the chip.) Groups past the table's k
    slots are dropped in key order — the numGroupsLimit trim — and their
    rows counted in the trash slot.

    `last` flags the ending rows, `key` is the per-row key, `live` the
    inclusive count of rows that take part, `cols` the per-row columns
    (None entries pass through), `n_live` the count of all live rows."""

    def __init__(self, last, key, live, cols, k: int, n_live):
        n = last.shape[0]
        out = _flagged_to_front(
            last, [key, live] + [c for c in cols if c is not None], k)
        self.row = out[0]
        self.occupied = self.row < _I32_MAX
        self.rowc = jnp.minimum(self.row, n - 1)
        it = iter(out[3:])
        self.cols = [None if c is None else next(it) for c in cols]
        counts_k = self.diff(out[2]).astype(jnp.int64)
        # trash slot counts live-but-trimmed rows, so the host can report
        # every post-filter doc as scanned even when the numGroupsLimit
        # trim drops groups
        self.counts = jnp.concatenate(
            [counts_k, (n_live.astype(jnp.int64) - counts_k.sum())[None]])
        self.keys = jnp.where(self.occupied, out[1].astype(jnp.int64),
                              jnp.int64(-1))

    def diff(self, prefix):
        """Per-slot totals from an inclusive prefix read at the ending
        rows: the difference to the slot before (live rows between two
        groups' ends belong to the later group; rows that take no part
        add nothing to any prefix)."""
        before = jnp.concatenate([jnp.zeros((1,), prefix.dtype), prefix[:-1]])
        return jnp.where(self.occupied, prefix - before,
                         jnp.zeros((), prefix.dtype))

    def at(self, col):
        """A per-row column NOT carried through the sort, gathered at the
        ending rows (the bitmap words of a DISTINCT: a column a word)."""
        return col[self.rowc]

    def output(self, kind: str, col):
        """(k+1,) f64 output column of a sum / min / max spec from its
        carried running column (`_running_columns`)."""
        if kind == "sum_i":
            return self.padded(self.diff(col), 0.0)
        if kind == "sum_f":
            return self.padded(col, 0.0)
        empty = jnp.inf if kind in ("min_i", "min_f") else -jnp.inf
        return self.padded(col.astype(jnp.float64), empty)

    def padded(self, slot_values, empty):
        """(k+1,) output column: empty slots and the trash slot read
        `empty`."""
        v = jnp.where(self.occupied, slot_values, empty)
        return jnp.concatenate([v, jnp.full((1,), empty, v.dtype)])


def _presorted_sparse_tail(program: ir.Program, operands, specs, mask, n):
    """Sorted-key fast path: the rows are never sorted by key (reference
    SortedGroupByOperator).

    The single key plane (operands[0], RAW — no sentinel) is nondecreasing
    over the segment (planner checked ColumnMetadata.is_sorted), so group
    runs are already contiguous in DOC order. Rows never move, which changes
    the bookkeeping versus the sorted path in two ways: masked rows (filter
    misses + the padded tail) sit INSIDE/AFTER runs instead of sorting to a
    sentinel tail, so

    - a group exists only where a key run has >= 1 masked-in row, and the
      run's LAST row carries its totals — fully-masked runs must not
      consume numGroupsLimit slots, or an exact ORDER BY trim could drop a
      live group that a sorted-path run would keep;
    - per-group reductions skip masked rows via op identities (the operand
      loop already substituted them) and counts come from a mask prefix sum.

    The padded tail (device planes pad dict id 0 past num_docs) would break
    the nondecreasing invariant, but those rows are always masked off
    (run_program ANDs the doc-count iota mask): they form runs of their own
    after the last real row, or lengthen its run, and add op identities
    either way. The one sort here is `_GroupEnds`': the ending rows move to
    the table's first slots.
    """
    key = operands[0]
    differs = key[1:] != key[:-1]
    first_key = jnp.concatenate([jnp.ones((1,), dtype=bool), differs])
    # running masked-in row count within each key run (inclusive): a run
    # with none forms no group, and a run's LAST row carries its totals
    mrun = _segmented_scan(mask.astype(jnp.int32), first_key, jnp.add)
    last = jnp.concatenate([differs, jnp.ones((1,), dtype=bool)]) \
        & (mrun > 0)
    # per-group masked-in row counts from one mask prefix sum: rows of
    # fully-masked runs between two groups contribute zero by construction
    pm = _prefix_sum(mask.astype(jnp.int32))

    # (masked rows hold their op's identity: the operand loop saw to it)
    cols = _running_columns(specs, operands, first_key)
    table = _GroupEnds(last, key, pm, cols, program.num_groups, pm[n - 1])

    outputs = [table.counts]
    for spec, col in zip(specs, table.cols):
        kind, oi = spec[0], spec[1]
        agg = spec[2] if len(spec) > 2 else None
        if kind == "count":
            outputs.append(table.counts)
        elif kind == "distinct":
            # ids are NOT sorted within a run here (no sort happened), so
            # the sorted path's uniq-row trick is unavailable — but OR is
            # idempotent, so the log2(n)-pass segmented OR scan builds the
            # same per-group bitmap words without dedup
            card = agg.card
            bit = operands[oi].astype(jnp.uint32)
            words = []
            for w in range(-(-card // 32)):
                val = jnp.where(mask & ((bit >> 5) == jnp.uint32(w)),
                                jnp.uint32(1) << (bit & jnp.uint32(31)),
                                jnp.uint32(0))
                word = table.at(
                    _segmented_scan(val, first_key, jnp.bitwise_or))
                words.append(jnp.where(table.occupied, word, jnp.uint32(0)))
            outputs.append(_bitmap_rows(words))
        elif col is None:
            # unbounded int64 columns keep the exact limb scatters; indices
            # are NOT flagged sorted (masked rows scatter into the trash)
            k = program.num_groups
            first = mask & (mrun == 1)
            gidx = _prefix_sum(first.astype(jnp.int32)) - 1
            gid = jnp.where(mask & (gidx >= 0) & (gidx < k),
                            gidx, jnp.int32(k))
            outputs.append(_segment_sum_exact_i64(
                operands[oi], gid, k + 1, n, agg.vmin, agg.vmax,
                indices_are_sorted=False).astype(jnp.float64))
        else:
            outputs.append(table.output(kind, col))
    outputs.append(table.keys)
    return tuple(outputs)


def _int_prefix_bound(agg):
    bound = max(abs(int(agg.vmin)), abs(int(agg.vmax))) \
        if agg is not None and agg.vmin is not None and agg.vmax is not None \
        else (1 << 31)
    block = 1 << max(0, min(11, 30 - bound.bit_length()))
    return bound, block


def _prefix_exact(agg, n: int) -> bool:
    """True when f64 prefix-diff sums of `n` rows of this integer
    operand are EXACT: every partial sum is an integer below 2^53."""
    bound, block = _int_prefix_bound(agg)
    return block >= 8 and n % block == 0 and n * bound < (1 << 53)


def _prefix_exact_gate(v, agg) -> bool:
    """`_prefix_exact` for a plane; floats take the segmented-scan
    sum_f path."""
    return not jnp.issubdtype(v.dtype, jnp.integer) \
        or _prefix_exact(agg, v.shape[0])


def _sorted_prefix_f64(v, agg):
    """Inclusive prefix sums (n,) f64 of an int column, EXACT under the
    _prefix_exact_gate bound: intra-block cumsums run in int32 sized so
    they cannot overflow, block totals accumulate in f64 where every
    partial sum is an integer below 2^53."""
    n = v.shape[0]
    _, block = _int_prefix_bound(agg)
    m = v.astype(jnp.int32).reshape(n // block, block)
    intra = _prefix_sum(m)  # exact: block * bound < 2^31
    inter = _prefix_sum(intra[:, -1].astype(jnp.float64))
    inter = jnp.concatenate([jnp.zeros(1), inter[:-1]])
    return (inter[:, None] + intra.astype(jnp.float64)).reshape(n)


def _delayed(x, d: int):
    """x shifted d places along its last axis (out[..., i] = x[..., i-d]);
    the first d places hold zeros/False."""
    pad = jnp.zeros(x.shape[:-1] + (d,), x.dtype)
    return jnp.concatenate([pad, x[..., :-d]], axis=-1)


def _prefix_sum(x):
    """Inclusive prefix sum along the last axis as log2(n) shift+add passes
    over contiguous slices. Bit-identical to jnp.cumsum for every dtype
    whose addition is exact (integers; f64 holding integers below 2^53) —
    the only dtypes callers pass. jnp.cumsum lowers to an odd/even
    recursive scan on TPU whose strided 1-D slices take the chip's compiler
    minutes at segment-sized n; this form compiles in seconds."""
    n = x.shape[-1]
    d = 1
    while d < n:
        x = x + _delayed(x, d)
        d *= 2
    return x


def _segmented_scan(v, first, op):
    """Per-segment running reduce over sorted data: at index i, op over
    v[segment_start..i]. log2(n) shift passes (see _prefix_sum) — no
    scatter, no recursive scan. Row 0 opens the first segment whatever its
    flag says; with it set, every row i < d already carries a flag at pass
    d and the zeros _delayed shifts in are never combined."""
    n = v.shape[-1]
    f = first.at[..., 0].set(True)
    d = 1
    while d < n:
        v = jnp.where(f, v, op(_delayed(v, d), v))
        f = f | _delayed(f, d)
        d *= 2
    return v


def _segment_sum_exact_i64(v, gid, num_segments, n, vmin=None, vmax=None,
                           indices_are_sorted=False):
    """Exact int64 per-segment sums built from int32 scatters.

    64-bit scatters are SOFTWARE-EMULATED on TPU (measured ~10x slower than
    the same scatter at 32 bits — the difference between 1.9s and 0.18s for
    16M rows), so the sum decomposes into b-bit limbs with b chosen so a
    per-group limb sum cannot overflow int32: rows * (2^b - 1) < 2^31.
    Negative values ride two's complement: sum(v) = sum(uint32(v)) - 2^32 *
    count(v < 0); the planner's static value bounds skip unreachable limbs
    and the negative-count pass entirely for non-negative columns."""
    v = v.astype(jnp.int32)
    u = v.astype(jnp.uint32)  # two's-complement reinterpretation
    b = max(1, min(16, 31 - max(1, n - 1).bit_length()))
    shifts, nonneg = _limb_shifts(vmin, vmax, b)
    total = jnp.zeros(num_segments, dtype=jnp.int64)
    for shift in shifts:
        limb = ((u >> shift) & jnp.uint32((1 << b) - 1)).astype(jnp.int32)
        s = jax.ops.segment_sum(limb, gid, num_segments=num_segments,
                                indices_are_sorted=indices_are_sorted)
        total = total + (s.astype(jnp.int64) << shift)
    if not nonneg:
        negs = jax.ops.segment_sum((v < 0).astype(jnp.int32), gid,
                                   num_segments=num_segments,
                                   indices_are_sorted=indices_are_sorted)
        total = total - (negs.astype(jnp.int64) << 32)
    return total


def _mxu_or_scatter_counts(mask, sid, num_slots):
    """Per-slot row counts: MXU one-hot matmul when the table fits its
    accumulator, 32-bit scatter otherwise. Returns (num_slots,) int64."""
    if mxu_groupby.supports(num_slots, 1):
        return mxu_groupby.limb_sums(
            (mask.astype(mxu_groupby.PLANE_DTYPE),), sid, num_slots)[0]
    return jax.ops.segment_sum(
        mask.astype(jnp.int32), sid,
        num_segments=num_slots).astype(jnp.int64)


_I32_MAX = (1 << 31) - 1
_I32_MIN = -(1 << 31)


def _limb_shifts(vmin, vmax, b):
    """Limb starting bits for an exact two's-complement int32 sum split
    into b-bit limbs, and whether the negative-count correction pass can be
    skipped (planner-proved non-negative columns)."""
    nonneg = vmin is not None and vmin >= 0
    nbits = 32
    if nonneg and vmax is not None:
        nbits = max(1, int(vmax).bit_length())
    return list(range(0, nbits, b)), nonneg


def _fits_i32(v, agg: ir.AggOp) -> bool:
    """The 32-bit fast paths are only sound when every value fits int32:
    either the plane is int32 already, or the planner proved bounds.
    LONG/TIMESTAMP columns are int64 planes — without bounds they take the
    float64 path (exact to 2^53, the pre-optimization behavior)."""
    if v.dtype == jnp.int32:
        return True
    return (agg.vmin is not None and agg.vmax is not None
            and agg.vmin >= _I32_MIN and agg.vmax <= _I32_MAX)


def _run_agg(agg: ir.AggOp, arrays, params, mask, gid, num_segments, n,
             counts=None):
    if agg.kind == "count":
        return jax.ops.segment_sum(mask.astype(jnp.int64), gid, num_segments=num_segments)
    if agg.kind in ("distinct_bitmap", "value_hist"):
        # per-(group, dictId) occupancy/count matrix — shipped to host so
        # distinct VALUE sets / exact value histograms (percentile, mode)
        # can merge across segments (dict ids are segment-local). When the
        # (groups x card) table fits the MXU accumulator, the counts ride
        # the one-hot matmul instead of a whole-column scatter (the
        # scatter unit costs ~7.7ns/row — ~0.8s per 100M-row pass).
        card = agg.card
        num_groups = num_segments - 1
        ids = arrays[agg.ids_slot].astype(jnp.int32)
        sid = gid * jnp.int32(card) + ids
        sid = jnp.where(mask, sid, jnp.int32(num_groups * card))
        occ = _mxu_or_scatter_counts(mask, sid, num_groups * card + 1)
        occ = occ[: num_groups * card].reshape(num_groups, card)
        return occ > 0 if agg.kind == "distinct_bitmap" else \
            occ.astype(jnp.int64)
    if agg.kind == "hist_adaptive":
        # percentile sketch: TWO MXU count passes replace the (groups x
        # 2048)-slot scatter histogram. Pass 1 bins values coarsely; the
        # per-group bucket holding the target quantile is found ON DEVICE
        # (cumsum over the small (groups, bins) table); pass 2 re-bins the
        # rows of exactly that bucket `bins`x finer. Effective resolution
        # at the quantile = range/bins^2 with 2*bins+1 output words per
        # group instead of 2048 (the reference's t-digest concentrates
        # centroids at the tails the same way; this concentrates around
        # the asked quantile).
        bins = agg.bins
        num_groups = num_segments - 1
        # the whole-column binning arithmetic runs in f32: the TPU has no
        # f64 ALU (XLA software-emulates it, ~10x), and bucket assignment
        # only needs edge precision — an edge-adjacent row landing one
        # bucket over moves the decoded quantile by ≤ 1 refined bucket,
        # already inside the stated range/bins^2 bound. The ONE op kept in
        # f64 is the (v - lo) rebase: casting v itself to f32 would round
        # by ulp(|v|), which for large-magnitude narrow-range columns
        # (epoch-millis) dwarfs the bucket width; the rebased offset has
        # magnitude ≤ (hi-lo) where f32 ulp is ~1e-7 of the range.
        # Membership between the two passes stays BIT-IDENTICAL because
        # pass 2 recomputes b1 with the same ops.
        lo64 = params[agg.lo_param]
        if agg.prebased:
            # the plane in HBM is already (v - lo) as f32 (the planner's
            # rawf32r slot; lo == the column min the plane was rebased by)
            v = _eval_value(agg.vexpr, arrays, params)
        else:
            v64 = _eval_value(agg.vexpr, arrays, params).astype(jnp.float64)
            v = (v64 - lo64).astype(jnp.float32)  # offset from lo, f32-safe
        span = jnp.float32(params[agg.hi_param] - lo64)
        width1 = span / bins
        b1 = jnp.clip((v / width1).astype(jnp.int32), 0, bins - 1)
        inside = mask & (v >= 0) & (v <= span)
        sid1 = jnp.where(inside, gid * jnp.int32(bins) + b1,
                         jnp.int32(num_groups * bins))
        h1 = _mxu_or_scatter_counts(inside, sid1, num_groups * bins + 1)
        h1 = h1[: num_groups * bins].reshape(num_groups, bins)
        cum = jnp.cumsum(h1, axis=1)
        rank = cum[:, -1].astype(jnp.float64) * (agg.pct / 100.0)
        bstar = jnp.argmax(cum.astype(jnp.float64) >= rank[:, None],
                           axis=1).astype(jnp.int32)
        # refine rows whose COARSE bin equals their group's target bucket
        # (b1 equality, not float range tests: bit-identical membership);
        # bucket offsets stay relative to lo, so all f32 magnitudes ≤ span
        bstar_pad = jnp.concatenate([bstar, jnp.zeros(1, jnp.int32)])
        bstar_r = bstar_pad[jnp.minimum(gid, num_groups)]
        lo_g = bstar.astype(jnp.float32) * width1
        lo_r = jnp.concatenate([lo_g, jnp.zeros(1, jnp.float32)])[
            jnp.minimum(gid, num_groups)]
        width2 = width1 / bins
        inside2 = inside & (b1 == bstar_r)
        b2 = jnp.clip(((v - lo_r) / width2).astype(jnp.int32), 0, bins - 1)
        sid2 = jnp.where(inside2, gid * jnp.int32(bins) + b2,
                         jnp.int32(num_groups * bins))
        h2 = _mxu_or_scatter_counts(inside2, sid2, num_groups * bins + 1)
        h2 = h2[: num_groups * bins].reshape(num_groups, bins)
        return jnp.concatenate(
            [h1, h2, bstar.astype(jnp.int64)[:, None]], axis=1)
    if agg.kind == "hist_fixed":
        # equal-width bins over [lo, hi]; out-of-range rows are dropped
        # (reference HistogramAggregationFunction semantics)
        bins = agg.bins
        num_groups = num_segments - 1
        v = _eval_value(agg.vexpr, arrays, params).astype(jnp.float64)
        lo = params[agg.lo_param]
        hi = params[agg.hi_param]
        width = (hi - lo) / bins
        b = jnp.clip(((v - lo) / width).astype(jnp.int32), 0, bins - 1)
        inside = mask & (v >= lo) & (v <= hi)
        sid = gid * jnp.int32(bins) + b
        sid = jnp.where(inside, sid, jnp.int32(num_groups * bins))
        counts = jax.ops.segment_sum(
            inside.astype(jnp.int32), sid, num_segments=num_groups * bins + 1
        ).astype(jnp.int64)
        return counts[: num_groups * bins].reshape(num_groups, bins)
    v = _eval_value(agg.vexpr, arrays, params)
    fast32 = jnp.issubdtype(v.dtype, jnp.integer) and _fits_i32(v, agg)
    if agg.kind in ("min", "max") and \
            min_max_form(num_segments - 1) == "reduce":
        return _min_max_reduce(agg.kind, v, mask, gid, num_segments,
                               counts if fast32 else None)
    if agg.kind == "sum":
        if fast32:
            vm = jnp.where(mask, _sum_value32(agg, v, arrays, params), 0)
            return _segment_sum_exact_i64(
                vm, gid, num_segments, n, agg.vmin, agg.vmax)
        v = jnp.where(mask, v, 0).astype(jnp.float64)
        return jax.ops.segment_sum(v, gid, num_segments=num_segments)
    if agg.kind == "sumsq":
        v = jnp.where(mask, v, 0).astype(jnp.float64)
        return jax.ops.segment_sum(v * v, gid, num_segments=num_segments)
    if agg.kind == "min":
        if fast32 and counts is not None:
            # masked rows route to the trash slot, so each group's scatter
            # sees only real values; EMPTY groups are detected by the count
            # column (never by a sentinel a real value could collide with)
            vm = jnp.where(mask, v.astype(jnp.int32), _I32_MAX)
            out = jax.ops.segment_min(vm, gid, num_segments=num_segments)
            return jnp.where(counts == 0, jnp.inf, out.astype(jnp.float64))
        if v.dtype == jnp.float32:
            vm = jnp.where(mask, v, jnp.float32(jnp.inf))
            return jax.ops.segment_min(
                vm, gid, num_segments=num_segments).astype(jnp.float64)
        v = jnp.where(mask, v, jnp.inf).astype(jnp.float64)
        return jax.ops.segment_min(v, gid, num_segments=num_segments)
    if agg.kind == "max":
        if fast32 and counts is not None:
            vm = jnp.where(mask, v.astype(jnp.int32), _I32_MIN)
            out = jax.ops.segment_max(vm, gid, num_segments=num_segments)
            return jnp.where(counts == 0, -jnp.inf, out.astype(jnp.float64))
        if v.dtype == jnp.float32:
            vm = jnp.where(mask, v, jnp.float32(-jnp.inf))
            return jax.ops.segment_max(
                vm, gid, num_segments=num_segments).astype(jnp.float64)
        v = jnp.where(mask, v, -jnp.inf).astype(jnp.float64)
        return jax.ops.segment_max(v, gid, num_segments=num_segments)
    raise ValueError(f"unknown agg kind {agg.kind}")


def _min_max_reduce(kind: str, v, mask, gid, num_segments: int, counts):
    """MIN or MAX of a dense group-by over a FEW groups with no scatter:
    out[g] = reduce(where(gid == g, v, identity)) for every group at once,
    one broadcast compare against the group numbers reduced over the rows.
    XLA fuses compare, select and reduce (a MIN and a MAX of one program
    into ONE fusion over the masked values and the group ids, which pass
    HBM as (rows,) planes as they did for the scatter), so nothing of
    (groups, rows) reaches HBM and the compile time does not grow with
    the groups (no chain of selects: `_dict_lookup` says what those
    cost). The output is `_run_agg`'s
    scatter form's bit for bit, on its three value paths: int32 with the
    empty groups read off `counts` (never off a sentinel that a value
    could equal), float32, and float64 for everything else. The trash
    slot holds the masked rows alone, which carry the identity: it is
    appended, not reduced."""
    lo = kind == "min"
    if counts is not None:
        ident = jnp.int32(_I32_MAX if lo else _I32_MIN)
        vm = jnp.where(mask, v.astype(jnp.int32), ident)
    elif v.dtype == jnp.float32:
        ident = jnp.float32(jnp.inf if lo else -jnp.inf)
        vm = jnp.where(mask, v, ident)
    else:
        ident = jnp.float64(jnp.inf if lo else -jnp.inf)
        vm = jnp.where(mask, v, ident).astype(jnp.float64)
    groups = jnp.arange(num_segments - 1, dtype=jnp.int32)
    picked = jnp.where(gid[None, :] == groups[:, None], vm[None, :], ident)
    out = picked.min(axis=1) if lo else picked.max(axis=1)
    out = jnp.concatenate([out, ident[None]]).astype(jnp.float64)
    if counts is None:
        return out
    return jnp.where(counts == 0, jnp.inf if lo else -jnp.inf, out)


# ---------------------------------------------------------------------------
# Device-side sparse combine (server-level merge of per-segment group tables)
# ---------------------------------------------------------------------------

# empty merged-table slots carry this key; above any real dictionary VALUE
# (sparse value-space keys are int64 dictionary values, not composite ids)
COMBINE_KEY_SENTINEL = 1 << 62
# a cut keeps this many slots at least: `k` is an argument of the program,
# the slots it is cut to are a shape (a power of two at or above `k`)
MIN_CUT_SLOTS = 1 << 13
# tables above this many slots get the merge's branch on how full they are:
# a table cut at numGroupsLimit (100,000 by default) is full where it
# matters, its merge is a few ms, and a second branch doubles the sorts the
# chip's compiler is given (PR 30: 292 s for 146 on the sandbox's host)
QUARTER_MERGE_ABOVE_SLOTS = 100_000


def _key_sentinel(dtype):
    return jnp.asarray(_I32_MAX if dtype == jnp.int32
                       else COMBINE_KEY_SENTINEL, dtype)


def _table_values(keys, source, how: str, dtype):
    """A family's sparse key outputs (S, K) (dict IDS; -1 = empty slot) in
    dictionary VALUE space. Dictionaries are segment-local (the same id
    means different values in different segments — engine/results.py), so
    cross-segment merge keys must be values. `how` says what `source` is:
    "base": dictionaries of consecutive integers, value = first value (S,)
    + id, no plane read; "plane": the dictionaries' values (S, D),
    zero-padded past each segment's own entries, which no id reaches,
    gathered by id; "values": `keys` are int64 values already (a table kept
    from an earlier request, `table_keys_to_values`). Empty slots map to
    the sort sentinel so they tail the merge."""
    if how == "values":  # int64, empty slots at COMBINE_KEY_SENTINEL
        return jnp.where(keys < COMBINE_KEY_SENTINEL, keys,
                         _key_sentinel(dtype)).astype(dtype)
    if how == "base":
        vals = keys + source[:, None]
    else:
        ids = jnp.clip(keys, 0, source.shape[1] - 1).astype(jnp.int32)
        vals = jnp.take_along_axis(source, ids, axis=1)
    return jnp.where(keys >= 0, vals.astype(dtype), _key_sentinel(dtype))


@partial(jax.jit, static_argnames=("how",))
def table_keys_to_values(keys, source, how: str):
    """`_table_values` as a program of its own: the int64 value-space key
    column of a table that is kept on the device for a later request."""
    return _table_values(keys, source, how, jnp.int64)


def _merge_group_tables(tables, k, threshold, *, how, key32: bool, kinds,
                        order, cut_slots: int, table_slots: int):
    """Merge a server's per-segment sparse group tables ON DEVICE, and cut
    the merged table there where the ordered server-level trim would
    (combine.trim_group_by): only what the host needs crosses.

    tables:  one entry a batch family (or lone segment), each
             (keys (S_i, K), source, counts (S_i, K+1), states): the
             sparse kernel's key column with what takes it to value space
             (`_table_values`, `how[i]`), its int64 count column (slot K =
             trash) and its (S_i, K+1) state columns, one per Program agg
             op in op order (count copies are int64, the rest f64)
    k, threshold: the trim's size and threshold (arguments, not shapes)
    key32:   merge on int32 keys (every dictionary's values fit; 64-bit
             sorts are emulated on the chip)
    kinds:   per state column "add" | "min" | "max"
    order:   None, or (output index | None, descending, ties_high): the
             trim's ORDER BY as the device ranks it — one count or state
             column (None: the key alone), then the key (merged rows are
             in ascending key order, so a tie falls by the lower row, or
             by the higher under `ties_high`)
    cut_slots:   slots of the cut table (0: no cut in this program)
    table_slots: slots of the whole merged table (0: it is not made)

    Returns (header, table):
      header int64 (5,): merged groups, groups entering the merge, docs
             scanned (trash included), trash, and whether `table` is the
             CUT (1) — else it is the whole table, or () where neither was
             asked for: the host reads the merged groups' count and asks
             again for a table of that size.
      table  (counts (T+1,), *states (T+1,), keys (T,)) in the per-segment
             output layout, so LoweredAgg.vec.extract decodes it unchanged;
             occupied slots first, in ascending key order.

    A segment's table holds its groups in its first slots, and a filter
    seldom leaves a group for every key of a dictionary: where no segment
    filled more than a quarter of its slots, the merge runs over the first
    quarters alone (`lax.cond`; the same program on a quarter of the rows).
    Tables of up to QUARTER_MERGE_ABOVE_SLOTS slots merge whole."""
    slots = tables[0][0].shape[1]

    def merge(width: int):
        dtype = jnp.int32 if key32 else jnp.int64
        key = jnp.concatenate([
            _table_values(t[0][:, :width], t[1], h, dtype).reshape(-1)
            for t, h in zip(tables, how)])
        cnt = jnp.concatenate([t[2][:, :width].reshape(-1) for t in tables])
        cols = [jnp.concatenate([t[3][i][:, :width].reshape(-1)
                                 for t in tables])
                for i in range(len(kinds))]
        return _merge_rows(key, cnt, cols, trash, k, threshold,
                           kinds=kinds, order=order, cut_slots=cut_slots,
                           table_slots=table_slots)

    # the scope is opened around the branch: a trace files an op under the
    # first scope of its name (`cond/branch_*` otherwise)
    with jax.named_scope("combine"):
        filled = jnp.max(jnp.stack(
            [(t[2][:, :-1] > 0).sum(axis=1).max() for t in tables]))
        trash = sum(t[2][:, -1].sum() for t in tables)
        if slots <= QUARTER_MERGE_ABOVE_SLOTS:
            return merge(slots)
        quarter = slots // 4
        return jax.lax.cond(filled <= quarter, lambda: merge(quarter),
                            lambda: merge(slots))


def _merge_rows(key, cnt, cols, trash, k, threshold, *, kinds, order,
                cut_slots: int, table_slots: int):
    """`_merge_group_tables` over the tables' slots laid end to end.

    Per-segment tables are key-sorted, so the merge is the per-segment
    kernel's own machinery over all the slots: one sort by key that
    carries the columns, segmented scans, and a group's totals in the row
    that ends it. The cut ranks an int64 exactly: a sum is an f64 that
    holds an integer (never through float32), thousands of groups share a
    value, and the k-th value is found by bisection on counts — no second
    sort; of the rows that tie with it, the ORDER BY's key takes the first.
    A column that holds a fraction, or an integer past 2^62, is not cut
    (header), and the host trims as before."""
    m = key.shape[0]
    combined = (cnt > 0).sum()
    # a segment's count fits int32 (its rows do): one word less to sort
    skey, cnt, *cols = jax.lax.sort(
        (key, cnt.astype(jnp.int32)) + tuple(cols), num_keys=1,
        is_stable=False)
    cnt = cnt.astype(jnp.int64)
    valid = skey < _key_sentinel(skey.dtype)
    differs = skey[1:] != skey[:-1]
    first = jnp.concatenate([jnp.ones((1,), bool), differs]) & valid
    last = jnp.concatenate([differs, jnp.ones((1,), bool)]) & valid
    totals = [_segmented_scan(cnt, first, jnp.add)]
    for v, kind in zip(cols, kinds):
        op = {"add": jnp.add, "min": jnp.minimum,
              "max": jnp.maximum}[kind]
        totals.append(_segmented_scan(v, first, op))
    groups = last.sum()
    scanned = cnt.sum() + trash
    is_cut = jnp.zeros((), bool)
    table = ()
    if cut_slots:
        keep, exact = _rows_of_the_cut(last, totals, order, k, groups)
        is_cut = exact & (groups > threshold) & (groups > k)
        # the kept rows, in row order: the j-th is where the running count
        # of kept rows first reaches j (k binary searches)
        at = jnp.searchsorted(
            _prefix_sum(keep.astype(jnp.int32)),
            jnp.arange(1, cut_slots + 1, dtype=jnp.int32))
        full = at < m
        at = jnp.minimum(at, m - 1)
        table = _table_of(full, skey[at], [t[at] for t in totals], kinds,
                          trash)
    elif table_slots:
        # the ending rows move to the front by one more sort that carries
        # the columns
        out = _flagged_to_front(last, [skey] + totals, table_slots)
        table = _table_of(out[0] < _I32_MAX, out[1], out[2:], kinds, trash)
    header = jnp.stack([groups.astype(jnp.int64),
                        combined.astype(jnp.int64), scanned, trash,
                        is_cut.astype(jnp.int64)])
    return header, table


def _rows_of_the_cut(last, totals, order, k, groups):
    """(keep, exact): the `k` ending rows that come first in the ORDER BY,
    and whether the ranked column could be ranked exactly."""
    col, descending, ties_high = order
    m = last.shape[0]
    nth = _prefix_sum(last.astype(jnp.int32))  # 1-based rank by key
    if col is None:  # the key alone: the first k groups, or the last
        keep = last & ((nth > groups - k) if descending else (nth <= k))
        return keep, jnp.ones((), bool)
    v = totals[col]
    if jnp.issubdtype(v.dtype, jnp.floating):
        vz = jnp.where(last, v, 0.0)
        exact = jnp.all((vz == jnp.floor(vz))
                        & (jnp.abs(vz) < float(1 << 62)))
        v = vz.astype(jnp.int64)
    else:
        exact = jnp.ones((), bool)
        v = v.astype(jnp.int64)
    lowest = jnp.int64(-(1 << 63))
    r = jnp.where(last, v if descending else -v, lowest)  # higher is first
    # the k-th highest rank, by bisection on the count of rows at or above
    lo = jnp.min(jnp.where(last, r, jnp.int64((1 << 63) - 1)))
    hi = jnp.max(r)

    def narrow(c):
        lo, hi, i = c
        mid = lo + (hi - lo + 1) // 2
        enough = (r >= mid).sum() >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1), \
            i + 1

    kth, _, _ = jax.lax.while_loop(
        lambda c: (c[0] < c[1]) & (c[2] < 64), narrow,
        (lo, hi, jnp.int32(0)))
    above = r > kth
    ties = last & (r == kth)
    want = k - above.sum()  # of the ties, by the key
    tie_nth = _prefix_sum(ties.astype(jnp.int32))
    if ties_high:
        taken = tie_nth > tie_nth[m - 1] - want
    else:
        taken = tie_nth <= want
    return above | (ties & taken), exact


def _table_of(occupied, key, totals, kinds, trash):
    """Slot columns in the per-segment output layout: counts (T+1,) with
    the trash slot last, a (T+1,) column per state, keys (T,)."""
    zero64 = jnp.zeros((), jnp.int64)
    outs = [jnp.concatenate([jnp.where(occupied, totals[0], zero64),
                             trash[None]])]
    for v, kind in zip(totals[1:], kinds):
        empty = jnp.asarray({"add": 0, "min": jnp.inf,
                             "max": -jnp.inf}[kind], v.dtype)
        outs.append(jnp.concatenate(
            [jnp.where(occupied, v, empty), empty[None]]))
    outs.append(jnp.where(occupied, key.astype(jnp.int64), jnp.int64(-1)))
    return tuple(outs)


merge_group_tables = jit_named(
    _merge_group_tables, "merge_group_tables",
    static_argnames=("how", "key32", "kinds", "order", "cut_slots",
                     "table_slots"))
