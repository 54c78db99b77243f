"""Per-segment query planner: QueryContext + segment → kernel Program.

Reference: pinot-core/.../plan/maker/InstancePlanMakerImplV2.java:275
(makeSegmentPlanNode dispatches on query shape) plus the predicate-evaluator
layer (pinot-core/.../operator/filter/predicate/PredicateEvaluatorProvider) —
there, predicates resolve against dictionaries at planning time; here that
resolution produces *device kernel parameters*: sorted dictionaries turn
value predicates into dict-id intervals or boolean LUTs, so the kernel never
touches a string.

Unsupported shapes raise UnsupportedQueryError and the caller falls back to
the host (numpy) engine — mirroring how the reference keeps the scalar path
as default (BASELINE.json north star).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field, replace
from typing import Optional

import numpy as np

from ..query.context import QueryContext
from ..query.expressions import ExpressionContext, is_aggregation
from ..query.filter import FilterContext, FilterNodeType, Predicate, PredicateType
from ..query.transforms import IRBuilder, eval_expr_np, get_transform
from ..segment.device_cache import SegmentDeviceView, pad_bucket
from ..segment.loader import ImmutableSegment
from ..ops import mxu_groupby
from ..spi.data_types import DataType
from . import ir
from .aggregation import (DENSE_GROUP_LIMIT, AggPlanContext, LoweredAgg,
                          UnsupportedQueryError, lower_aggregation)

# DENSE_GROUP_LIMIT (re-exported from .aggregation): dense segment_sum
# HBM ceiling shared with the approximate-agg occupancy gate
SPARSE_KEY_LIMIT = ir.SPARSE_KEY_SPACE  # keys stay below the kernel sentinel
SPARSE_GROUPS_LIMIT = 1 << 25  # cap on sparse output table slots (~256MB/agg)
DEFAULT_NUM_GROUPS_LIMIT = 100_000  # reference InstancePlanMakerImplV2 default
_SPARSE_AGG_KINDS = {"count", "sum", "sumsq", "min", "max"}


def _key_space_bucket(num_groups: int) -> int:
    """Program.key_space for a sparse group-by: an upper bound the kernel
    only uses to pick 32-bit keys, so it is rounded up to a power of two
    (kept below the int32 sentinel where the exact bound is). Segments
    whose dictionaries differ in size then share ONE program — one compile
    and one batch family, as `executor._dict_pad` does for dict planes —
    instead of one program per segment."""
    bucket = 1 << max(0, int(num_groups) - 1).bit_length()
    i32_top = (1 << 31) - 2
    return i32_top if num_groups <= i32_top < bucket else bucket


def table_bucket(card: int) -> int:
    """Slots of a sort-based table that holds every key of a dictionary of
    `card` entries: `card` rounded up to an eighth of its power of two, so
    that segments whose dictionaries differ by a few entries share one
    Program (and `executor.batch_family_key`) at no more than an eighth of
    empty slots."""
    step = 1 << max(0, int(card).bit_length() - 4)
    return -(-int(card) // step) * step


def row_major_strides(cards) -> list:
    """Strides of the composite group key Σ id_i · stride_i over keys of
    `cards` entries, the last key fastest (reference
    DictionaryBasedGroupKeyGenerator:119-137)."""
    strides = [1] * len(cards)
    for i in range(len(cards) - 2, -1, -1):
        strides[i] = strides[i + 1] * cards[i + 1]
    return strides


def _vexpr_uses_slots(ve, slots: set) -> bool:
    """True when a value expression reads any of the given array slots."""
    if ve is None:
        return False
    if isinstance(ve, (ir.Col, ir.IdsCol)):
        return ve.slot in slots
    if isinstance(ve, ir.DictGather):
        return ve.ids_slot in slots or ve.dict_slot in slots
    if isinstance(ve, ir.MvLutReduce):
        return True  # always reads an MV matrix
    if isinstance(ve, ir.ParamGather):
        return _vexpr_uses_slots(ve.ids, slots)
    if isinstance(ve, ir.Bin):
        return _vexpr_uses_slots(ve.a, slots) or _vexpr_uses_slots(ve.b, slots)
    if isinstance(ve, ir.Un):
        return _vexpr_uses_slots(ve.a, slots)
    if isinstance(ve, ir.Cast):
        return _vexpr_uses_slots(ve.a, slots)
    if isinstance(ve, ir.Where):
        return (_vexpr_uses_slots(ve.cond, slots)
                or _vexpr_uses_slots(ve.a, slots)
                or _vexpr_uses_slots(ve.b, slots))
    if isinstance(ve, ir.FilterVal):
        return _filter_uses_slots(ve.filter, slots)
    return False


def _filter_uses_slots(f, slots: set) -> bool:
    if isinstance(f, (ir.FAnd, ir.FOr)):
        return any(_filter_uses_slots(c, slots) for c in f.children)
    if isinstance(f, ir.FNot):
        return _filter_uses_slots(f.child, slots)
    if isinstance(f, ir.Lut):
        return f.ids_slot in slots
    if isinstance(f, ir.Null):
        return f.null_slot in slots
    if isinstance(f, ir.Interval):
        return _vexpr_uses_slots(f.vexpr, slots)
    if isinstance(f, ir.Isin):
        return _vexpr_uses_slots(f.vexpr, slots)
    return False


def _orderby_prefix_trim(q) -> "int | None":
    """offset+limit when ORDER BY is ALL the group-by keys, in stride
    order, all ASC with default null ordering and no HAVING — the shape
    where a per-segment keep-smallest-L composite trim cannot change the
    final result. The cover must be FULL: with a shorter prefix, a group
    trimmed in one segment but kept in another could be selected on a
    prefix tie with an incomplete aggregate unless the broker reduce
    tie-broke on the remaining keys (it doesn't on the dict-merge path)."""
    if q.having_filter is not None or not q.order_by_expressions:
        return None
    gb = q.group_by_expressions
    if q.distinct and not q.is_aggregation_query:
        gb = q.select_expressions
    obs = q.order_by_expressions
    if not gb or len(obs) != len(gb):
        return None
    for ob, ge in zip(obs, gb):
        if not ob.ascending or ob.nulls_last is not None \
                or str(ob.expression) != str(ge):
            return None
    return int(q.offset) + int(q.limit)


@dataclass
class GroupDim:
    column: str
    cardinality: int
    dictionary: object  # segment Dictionary (host) — decodes ids at combine


@dataclass
class DerivedDictionary:
    """Group-index → value table for a derived (expression) dimension."""

    values: np.ndarray


def collect_identifiers(e: ExpressionContext) -> set:
    out = set()
    if e.is_identifier:
        out.add(e.identifier)
    elif e.is_function:
        for a in e.function.arguments:
            out |= collect_identifiers(a)
    return out


def _coerce_like(vals: np.ndarray, v):
    """Coerce a predicate literal to the transformed-value dtype."""
    if vals.dtype.kind in "if":
        if isinstance(v, bool):
            return int(v)
        if isinstance(v, str):
            try:
                return float(v) if vals.dtype.kind == "f" else int(float(v))
            except ValueError:
                return v
        return v
    return str(v)


@dataclass
class SegmentPlan:
    program: ir.Program
    slots: list  # (column, kind); kind ∈ ids|mvids|raw|rawf32r|dict|null
    params: list  # host param values in order (np scalars / arrays)
    lowered_aggs: list[LoweredAgg] = field(default_factory=list)
    group_dims: list[GroupDim] = field(default_factory=list)
    selection_columns: list[str] = field(default_factory=list)
    selection_exprs: dict = field(default_factory=dict)  # label → transform expr
    # per-query kill switch for the single-pass fused kernel
    # (SET useFusedKernel = false — reference pattern: per-query engine
    # toggles like useStarTree applied by the plan maker)
    fused_ok: bool = True
    # why the group table is dense or sorted (EXPLAIN IMPLEMENTATION)
    group_table_reason: str = ""

    def gather_arrays(self, view: SegmentDeviceView) -> tuple:
        return self.gather_arrays_packed(view, allow_packed=False)[0]

    def gather_arrays_packed(self, view: SegmentDeviceView,
                             allow_packed: bool = True):
        """(arrays, packed) where packed lists (slot, bits) for id planes
        kept packed in HBM — decoded in-kernel (ops/kernels._apply_packed)."""
        out = []
        packed = []
        for i, (column, kind) in enumerate(self.slots):
            if kind == "ids":
                if allow_packed:
                    plane, bits = view.dict_ids_packed(column)
                    out.append(plane)
                    if bits:
                        packed.append((i, bits))
                else:
                    out.append(view.dict_ids(column))
            elif kind == "mvids":
                out.append(view.mv_dict_ids(column))
            elif kind == "raw":
                out.append(view.raw(column))
            elif kind == "rawf32r":
                out.append(view.raw_f32_rebased(column))
            elif kind == "dict":
                out.append(view.dict_values(column))
            elif kind == "null":
                out.append(view.null_plane(column))
            else:  # pragma: no cover
                raise ValueError(kind)
        return tuple(out), tuple(packed)


def share_table_size(plans: list) -> list:
    """Sorted group tables of one query's segments, sized alike. A sorted
    table's slots follow the segment's own dictionary (`table_bucket`) or
    numGroupsLimit, whichever is less, so segments of one table can differ
    in `num_groups` and `key_space` and in nothing else, and would then
    compile and dispatch one by one. A table with more slots than its
    segment can fill answers the same, and a table cut at numGroupsLimit is
    never the smaller one, so every plan takes the largest of each; plans
    that differ in more are returned as they are."""
    programs = [pl.program for pl in plans]
    if len(plans) < 2 or any(p.mode != "group_by_sparse" for p in programs):
        return plans
    size = dict(num_groups=max(p.num_groups for p in programs),
                key_space=max(p.key_space for p in programs))
    shared = [replace(p, **size) for p in programs]
    if any(p != shared[0] for p in shared):
        return plans
    return [pl if pl.program == p else replace(pl, program=p)
            for pl, p in zip(plans, shared)]


class SegmentPlanner(AggPlanContext):
    # realtime/device_plane.py's planner subclass lifts this: a pinned
    # MutableSegmentView exposes enough immutable state (snapshot dict,
    # pinned metadata, pinned validity) to lower device plans safely
    allow_mutable = False

    def __init__(self, query: QueryContext, segment: ImmutableSegment):
        super().__init__()
        if not self.allow_mutable and getattr(segment, "is_mutable", False):
            raise UnsupportedQueryError(
                "consuming (mutable) segments execute on the host engine")
        self.query = query
        self.segment = segment
        self._slots: list[tuple[str, str]] = []
        self._slot_index: dict[tuple[str, str], int] = {}
        self._params: list = []
        # advanced null handling: see QueryContext.null_handling
        self.null_handling = query.null_handling

    # -- slot/param bookkeeping -------------------------------------------
    def slot(self, column: str, kind: str) -> int:
        key = (column, kind)
        if key not in self._slot_index:
            self._slot_index[key] = len(self._slots)
            self._slots.append(key)
        return self._slot_index[key]

    def param(self, value) -> int:
        self._params.append(value)
        return len(self._params) - 1

    # -- column helpers ----------------------------------------------------
    def _meta(self, column: str):
        if not self.segment.has_column(column):
            raise UnsupportedQueryError(f"unknown column {column}")
        return self.segment.column_metadata(column)

    def dict_info(self, e: ExpressionContext, sv_only: bool = False):
        if not e.is_identifier or e.identifier == "*":
            return None
        m = self._meta(e.identifier)
        if m.encoding != "DICT":
            return None
        if sv_only and not m.single_value:
            return None
        kind = "ids" if m.single_value else "mvids"
        return self.slot(e.identifier, kind), m.cardinality, self.segment.get_dictionary(e.identifier)

    def _null_cond_for(self, e: ExpressionContext):
        """Boolean ValueExpr true where any column referenced by e is null
        (a transform over a null input is null — reference semantics), or
        None when advanced null handling is off / no referenced column is
        nullable."""
        if not self.null_handling:
            return None
        cond = None
        for c in sorted(e.columns()):
            if c == "*" or not self.segment.has_column(c) \
                    or not self._meta(c).has_nulls:
                continue
            nc = ir.NullCol(self.slot(c, "null"))
            cond = nc if cond is None else ir.Bin("or", cond, nc)
        return cond

    def agg_operand(self, e: ExpressionContext, identity):
        """value_expr wrapped so null rows contribute the agg identity
        (advanced null handling). identity: 0 | "inf" | "-inf"."""
        ve = self.value_expr(e)
        cond = self._null_cond_for(e)
        if cond is None:
            return ve
        if identity in ("inf", "-inf"):
            # min/max compare in f64 so ±inf identities exist for any dtype
            ve = ir.Cast(ve, "DOUBLE")
            ident = ir.ConstParam(self.param(
                np.float64(np.inf if identity == "inf" else -np.inf)))
        else:
            ident = ir.ConstParam(self.param(np.int64(identity)))
        return ir.Where(cond, ident, ve)

    def nonnull_count_op(self, e: ExpressionContext) -> int:
        """Kernel output index holding the per-group NON-NULL count of e;
        0 (the group doc count) when nulls cannot occur."""
        cond = self._null_cond_for(e)
        if cond is None:
            return 0
        one = ir.ConstParam(self.param(np.int32(1)))
        zero = ir.ConstParam(self.param(np.int32(0)))
        return self.add_op(ir.AggOp(
            "sum", vexpr=ir.Where(cond, zero, one), vmin=0, vmax=1))

    def mv_reduce_expr(self, e: ExpressionContext, op: str):
        """(vexpr, vmin, vmax) per-doc reduce of a numeric MV dict column
        (for SUMMV-family aggs): lut[id] over the (docs, max_mv) id matrix
        with the pad sentinel's lut slot holding the op identity, so
        row-reduces need no mask. op="count" is a param-free non-sentinel
        count. vmin/vmax bound the per-doc result when known (lets integer
        sums take the exact kernel paths). None → host fallback
        (raw/var-width/non-numeric MV)."""
        if not e.is_identifier:
            return None
        m = self._meta(e.identifier)
        if m.single_value or m.encoding != "DICT":
            return None
        slot, card, d = self.dict_info(e)
        max_mv = max(1, m.max_number_of_multi_values)
        if op == "count":
            return ir.MvLutReduce(slot, None, "count", card=card), 0, max_mv
        vals = np.asarray(d.values)
        if vals.dtype.kind not in "iuf" or not len(vals):
            return None  # non-numeric, or every row empty (no dictionary)
        if op == "sum" and vals.dtype.kind in "iu":
            # int64 entries and int64 row-sums: exact, like the host's
            # np.sum over the flattened int column
            lut = np.concatenate([vals.astype(np.int64),
                                  np.zeros(1, np.int64)])
            vmin = min(0, max_mv * int(vals[0]))
            vmax = max(0, max_mv * int(vals[-1]))
            return ir.MvLutReduce(slot, self.param(lut), "sum"), vmin, vmax
        ident = {"sum": 0.0, "min": np.inf, "max": -np.inf}[op]
        lut = np.concatenate([vals.astype(np.float64), [ident]])
        return ir.MvLutReduce(slot, self.param(lut), op), None, None

    def col_meta(self, e: ExpressionContext):
        if not e.is_identifier:
            return None
        return self._meta(e.identifier)

    def _fused_ok(self) -> bool:
        # case-insensitive off-spellings: options arrive as raw strings
        # through the distributed request path (mse/runtime._null_handling
        # normalizes the same way)
        opt = self.query.query_options.get("useFusedKernel")
        return str(opt).lower() not in ("false", "0", "off")

    def col_minmax(self, e: ExpressionContext):
        """(min, max) stats for a plain numeric column, else None — feeds
        fixed-bin device histograms (percentile approx on raw columns)."""
        if not e.is_identifier:
            return None
        m = self._meta(e.identifier)
        if m.min_value is None or m.max_value is None:
            return None
        if not DataType(m.data_type).is_numeric:
            return None
        return m.min_value, m.max_value

    def int_bounds(self, e: ExpressionContext):
        """(lo, hi) of an integer expression from static facts alone: an
        integer literal, a single-value integer column with min/max
        stats, and +, - and * of such by interval arithmetic; None for
        anything else. Exact: only an aggregation's operand is quantised
        (`aggregation._int_bounds`), since quantising each factor widens a
        product (TPC-H Q1's charge: 37 bits, 39 from quantised factors)."""
        if e.is_literal:
            v = e.literal
            ok = isinstance(v, int) and not isinstance(v, bool)
            return (v, v) if ok else None
        if e.is_identifier:
            if e.identifier == "*":
                return None
            m = self._meta(e.identifier)
            mm = self.col_minmax(e)
            if mm is None or not m.single_value \
                    or not DataType(m.data_type).is_integral \
                    or not all(isinstance(x, (int, np.integer)) for x in mm):
                return None
            return int(mm[0]), int(mm[1])
        fn = e.function
        if fn is None or fn.name not in ("plus", "minus", "times") \
                or len(fn.arguments) != 2:
            return None
        a, b = (self.int_bounds(x) for x in fn.arguments)
        if a is None or b is None:
            return None
        if fn.name == "plus":
            return a[0] + b[0], a[1] + b[1]
        if fn.name == "minus":
            return a[0] - b[1], a[1] - b[0]
        corners = [x * y for x in a for y in b]
        return min(corners), max(corners)

    def padded_rows(self) -> int:
        return pad_bucket(max(1, self.segment.num_docs))

    def _widening_product(self, e: ExpressionContext) -> bool:
        """A product of two factors that fit int32 whose own bounds do
        not: `mulw`, exact in int64 (the kernel forms its 64-bit words in
        32-bit multiplies), where int32 factors would wrap."""
        fits = lambda r: r is not None and -(1 << 31) <= r[0] \
            and r[1] < (1 << 31)
        return all(fits(self.int_bounds(x)) for x in e.function.arguments) \
            and not fits(self.int_bounds(e))

    # -- value expressions (device transform functions) --------------------
    def value_expr(self, e: ExpressionContext) -> ir.ValueExpr:
        if e.is_literal:
            v = e.literal
            if isinstance(v, bool):
                v = int(v)
            if not isinstance(v, (int, float)):
                raise UnsupportedQueryError(f"non-numeric literal in value context: {v!r}")
            return ir.ConstParam(self.param(np.float64(v) if isinstance(v, float) else np.int64(v)))
        if e.is_identifier:
            m = self._meta(e.identifier)
            if not m.single_value:
                raise UnsupportedQueryError(f"MV column {e.identifier} in value context")
            dt = DataType(m.data_type)
            if not dt.is_fixed_width:
                raise UnsupportedQueryError(f"var-width column {e.identifier} in value context")
            if m.encoding == "RAW":
                return ir.Col(self.slot(e.identifier, "raw"))
            return ir.DictGather(self.slot(e.identifier, "ids"), self.slot(e.identifier, "dict"))
        fn = e.function
        name, args = fn.name, fn.arguments
        if name in _BIN_FN:
            op = _BIN_FN[name]
            if op == "mul" and self._widening_product(e):
                op = "mulw"
            return ir.Bin(op, self.value_expr(args[0]), self.value_expr(args[1]))
        if name in _UN_FN:
            return ir.Un(_UN_FN[name], self.value_expr(args[0]))
        if name == "cast":
            return ir.Cast(self.value_expr(args[0]), str(args[1].literal).upper())
        if name == "case":
            # case(c1,v1,c2,v2,...,else) → nested Where
            pairs = args[:-1]
            out = self.value_expr(args[-1])
            for i in range(len(pairs) - 2, -1, -2):
                out = ir.Where(self.value_expr(pairs[i]), self.value_expr(pairs[i + 1]), out)
            return out
        if name == "coalesce" and args and args[0].is_identifier:
            m = self._meta(args[0].identifier)
            base = self.value_expr(args[0])
            if not m.has_nulls or len(args) < 2:
                return base
            null_slot = self.slot(args[0].identifier, "null")
            return ir.Where(ir.Un("not", ir.Col(null_slot)), base, self.value_expr(args[1]))
        td = get_transform(name)
        if td is not None and td.lower is not None:
            try:
                return td.lower(IRBuilder(self), list(args))
            except (UnsupportedQueryError, ValueError, KeyError):
                pass
        ve = self._dict_transform_expr(e)
        if ve is not None:
            return ve
        raise UnsupportedQueryError(f"transform function {name} not lowered to device")

    DICT_TRANSFORM_LIMIT = 1 << 18  # max cartesian LUT size for 2-col transforms
    # single-column LUTs scale linearly with cardinality (no cartesian
    # blowup): allow dimension-scale columns (LOOKUP joins over ~1M-row
    # dim tables ride a fk-cardinality LUT)
    DICT_TRANSFORM_LIMIT_1COL = 1 << 21

    def _dict_transform_expr(self, e: ExpressionContext) -> Optional[ir.ValueExpr]:
        """Numeric-valued transform over dict-encoded SV columns → evaluate
        over the DICTIONARY (or the cartesian product of two dictionaries) on
        host, ship the result as a LUT param, gather by (joint) dict id on
        device (ir.ParamGather)."""
        prep = self._dict_transform_values(e)
        if prep is None:
            return None
        index_vexpr, out = prep
        if out.dtype.kind == "b":
            out = out.astype(np.int64)
        if out.dtype.kind not in "if":
            return None  # string-valued: usable for predicates/group-by only
        return ir.ParamGather(index_vexpr, self.param(out))

    def _dict_transform_values(self, e: ExpressionContext):
        """(joint-id ValueExpr, transform(dictionary values)) when e is a
        function of 1-2 dict-encoded SV columns, else None. For two columns
        the LUT covers the cardinality cartesian product and the joint id is
        id_a * card_b + id_b — same arithmetic as the dense group key."""
        cols = sorted(collect_identifiers(e))
        if not 1 <= len(cols) <= 2:
            return None
        infos = []
        product = 1
        for c in cols:
            if not self.segment.has_column(c):
                return None
            m = self.segment.column_metadata(c)
            if m.encoding != "DICT" or not m.single_value:
                return None
            vals = np.asarray(self.segment.get_dictionary(c).values)
            infos.append((c, len(vals), vals))
            product *= len(vals)
        limit = (self.DICT_TRANSFORM_LIMIT_1COL if len(infos) == 1
                 else self.DICT_TRANSFORM_LIMIT)
        if product > limit:
            return None
        if len(infos) == 1:
            c, _, vals = infos[0]
            grids = {c: vals}
            index_vexpr: ir.ValueExpr = ir.IdsCol(self.slot(c, "ids"))
        else:
            (c1, k1, v1), (c2, k2, v2) = infos
            grids = {c1: np.repeat(v1, k2), c2: np.tile(v2, k1)}
            index_vexpr = ir.Bin(
                "add",
                ir.Bin("mul", ir.IdsCol(self.slot(c1, "ids")),
                       ir.ConstParam(self.param(np.int32(k2)))),
                ir.IdsCol(self.slot(c2, "ids")))
        try:
            out = eval_expr_np(e, lambda name: grids[name])
        except (UnsupportedQueryError, ValueError, KeyError, TypeError):
            return None
        out = np.asarray(out)
        if out.shape != (product,):
            out = np.broadcast_to(out, (product,)).copy()
        return index_vexpr, out

    def _derived_dim(self, ge: ExpressionContext):
        """Group-by key = transform of one dict column: transform the
        dictionary on host, unique the results, remap dict ids → dense group
        ids through a LUT gather. Covers GROUP BY year(ts), upper(name),
        substr(c,0,3)... with the same dense segment_sum fast path."""
        prep = self._dict_transform_values(ge)
        if prep is None:
            return None
        index_vexpr, out = prep
        uniq, inv = np.unique(out, return_inverse=True)
        vexpr = ir.ParamGather(index_vexpr, self.param(inv.astype(np.int32)))
        return vexpr, len(uniq), DerivedDictionary(uniq)

    # -- filter lowering ---------------------------------------------------
    def lower_filter(self, f: Optional[FilterContext]) -> Optional[ir.FilterNode]:
        if f is None:
            return None
        if self.null_handling:
            true_node, _unknown = self._lower_filter3(f)
            return true_node
        return self._lower_filter(f)

    def _lower_filter(self, f: FilterContext) -> ir.FilterNode:
        if f.type == FilterNodeType.AND:
            return ir.FAnd(tuple(self._lower_filter(c) for c in f.children))
        if f.type == FilterNodeType.OR:
            return ir.FOr(tuple(self._lower_filter(c) for c in f.children))
        if f.type == FilterNodeType.NOT:
            return ir.FNot(self._lower_filter(f.children[0]))
        if f.type == FilterNodeType.CONSTANT:
            return ir.FConst(f.constant_value)
        return self._lower_predicate(f.predicate)

    # -- 3-valued lowering (advanced null handling) ------------------------
    def _lower_filter3(self, f: FilterContext):
        """Kleene logic as a (definitely-true, unknown) node pair — NOT of
        unknown stays unknown (excluded), but a child whose truth is
        DEFINED for null rows (IS NULL, constants, an OR with a true arm)
        keeps them. The final filter is the definitely-true mask."""
        FALSE = ir.FConst(False)

        def is_false(n):
            return isinstance(n, ir.FConst) and not n.value

        if f.type == FilterNodeType.AND:
            ts, us = zip(*(self._lower_filter3(c) for c in f.children))
            t = ir.FAnd(tuple(ts))
            if all(is_false(u) for u in us):
                return t, FALSE
            # unknown: every child true-or-unknown, not all definitely true
            tu = ir.FAnd(tuple(ti if is_false(ui) else ir.FOr((ti, ui))
                               for ti, ui in zip(ts, us)))
            return t, ir.FAnd((tu, ir.FNot(t)))
        if f.type == FilterNodeType.OR:
            ts, us = zip(*(self._lower_filter3(c) for c in f.children))
            t = ir.FOr(tuple(ts))
            if all(is_false(u) for u in us):
                return t, FALSE
            return t, ir.FAnd((ir.FOr(tuple(u for u in us if not is_false(u))),
                               ir.FNot(t)))
        if f.type == FilterNodeType.NOT:
            ct, cu = self._lower_filter3(f.children[0])
            if is_false(cu):
                return ir.FNot(ct), FALSE
            # true ↔ child definitely false; unknown unchanged
            return ir.FAnd((ir.FNot(ct), ir.FNot(cu))), cu
        if f.type == FilterNodeType.CONSTANT:
            return ir.FConst(f.constant_value), FALSE
        p = f.predicate
        node = self._lower_predicate(p)
        if p.type in (PredicateType.IS_NULL, PredicateType.IS_NOT_NULL):
            return node, FALSE  # defined for every row
        unknown = None
        for c in sorted(p.lhs.columns()):
            if self.segment.has_column(c) and self._meta(c).has_nulls:
                nc = ir.Null(self.slot(c, "null"))
                unknown = nc if unknown is None else ir.FOr((unknown, nc))
        if unknown is None:
            return node, FALSE
        return ir.FAnd((node, ir.FNot(unknown))), unknown

    def _lower_predicate(self, p: Predicate) -> ir.FilterNode:
        lhs = p.lhs
        if p.type in (PredicateType.JSON_MATCH, PredicateType.TEXT_MATCH,
                      PredicateType.VECTOR_SIMILARITY):
            return self._lower_host_mask(p)
        if p.type in (PredicateType.IS_NULL, PredicateType.IS_NOT_NULL):
            if not lhs.is_identifier:
                raise UnsupportedQueryError("IS NULL on expressions unsupported")
            m = self._meta(lhs.identifier)
            if not m.has_nulls:
                node = ir.FConst(False)
            else:
                node = ir.Null(self.slot(lhs.identifier, "null"))
            return ir.FNot(node) if p.type == PredicateType.IS_NOT_NULL else node

        info = self.dict_info(lhs) if lhs.is_identifier else None
        if info is not None:
            return self._lower_dict_predicate(p, lhs, info)
        if lhs.is_function:
            # mapvalue(col,'key') over a map index: dense-plane compare on
            # host → mask param (the map-index analogue of _lower_host_mask)
            from .host_executor import eval_map_index_predicate

            mm = eval_map_index_predicate(p, self.segment)
            if mm is not None:
                return self._mask_param(mm)
            try:
                return self._lower_value_predicate(p)
            except UnsupportedQueryError:
                node = self._lower_fn_dict_predicate(p)
                if node is not None:
                    return node
                raise
        return self._lower_value_predicate(p)

    def _lower_fn_dict_predicate(self, p: Predicate) -> Optional[ir.FilterNode]:
        """Predicate over a (possibly string-valued) transform of one dict
        column: evaluate transform + predicate against the dictionary on host
        → boolean LUT over dict ids (e.g. WHERE upper(name) = 'BOS')."""
        prep = self._dict_transform_values(p.lhs)
        if prep is None:
            return None
        index_vexpr, vals = prep
        card = len(vals)
        m = np.zeros(card, dtype=bool)
        if p.type in (PredicateType.EQ, PredicateType.NOT_EQ):
            m = vals == _coerce_like(vals, p.values[0])
            if p.type == PredicateType.NOT_EQ:
                m = ~m
        elif p.type in (PredicateType.IN, PredicateType.NOT_IN):
            for v in p.values:
                m |= vals == _coerce_like(vals, v)
            if p.type == PredicateType.NOT_IN:
                m = ~m
        elif p.type == PredicateType.RANGE:
            m = np.ones(card, dtype=bool)
            if p.lower is not None:
                lo = _coerce_like(vals, p.lower)
                m &= (vals >= lo) if p.lower_inclusive else (vals > lo)
            if p.upper is not None:
                hi = _coerce_like(vals, p.upper)
                m &= (vals <= hi) if p.upper_inclusive else (vals < hi)
        elif p.type in (PredicateType.LIKE, PredicateType.REGEXP_LIKE):
            regex = (like_to_regex(p.values[0]) if p.type == PredicateType.LIKE
                     else re.compile(str(p.values[0])))
            m = np.asarray([regex.search(str(x)) is not None for x in vals], dtype=bool)
        else:
            return None
        if isinstance(index_vexpr, ir.IdsCol):
            lut = np.zeros(card + 1, dtype=bool)
            lut[:card] = m
            return ir.Lut(index_vexpr.slot, self.param(lut), mv=False)
        # joint-id LUT: gather 0/1 then compare (ids never exceed the product)
        pi = self.param(np.int32(1))
        return ir.Interval(
            ir.ParamGather(index_vexpr, self.param(m.astype(np.int32))),
            lo_param=pi, hi_param=pi)

    def _lower_dict_predicate(self, p: Predicate, lhs, info) -> ir.FilterNode:
        ids_slot, card, d = info
        m = self._meta(lhs.identifier)
        mv = not m.single_value
        dt = DataType(m.data_type)

        def coerce(v):
            if dt.is_numeric and isinstance(v, bool):
                return int(v)
            return v

        if p.type in (PredicateType.EQ, PredicateType.NOT_EQ):
            did = d.index_of(coerce(p.values[0]))
            if mv:
                # MV predicate semantics are per-VALUE ("any value matches"),
                # so NOT_EQ needs an inverted LUT, not a document-level NOT
                lut = np.zeros(card + 1, dtype=bool)
                if did >= 0:
                    lut[did] = True
                if p.type == PredicateType.NOT_EQ:
                    lut[:card] = ~lut[:card]
                return ir.Lut(ids_slot, self.param(lut), mv=True)
            if did < 0:
                node = ir.FConst(False)
            else:
                node = self._id_interval(ids_slot, did, did, mv, card)
            return ir.FNot(node) if p.type == PredicateType.NOT_EQ else node

        if p.type == PredicateType.RANGE:
            lo_id = 0
            hi_id = card - 1
            if p.lower is not None:
                lo_id = d.insertion_index(coerce(p.lower), "left" if p.lower_inclusive else "right")
            if p.upper is not None:
                hi_id = d.insertion_index(coerce(p.upper), "right" if p.upper_inclusive else "left") - 1
            if lo_id > hi_id:
                return ir.FConst(False)
            if lo_id <= 0 and hi_id >= card - 1 and not mv:
                return ir.FConst(True)
            return self._id_interval(ids_slot, lo_id, hi_id, mv, card)

        if p.type in (PredicateType.IN, PredicateType.NOT_IN):
            lut = np.zeros(card + 1, dtype=bool)
            for v in p.values:
                did = d.index_of(coerce(v))
                if did >= 0:
                    lut[did] = True
            if p.type == PredicateType.NOT_IN:
                lut[:card] = ~lut[:card]
            return ir.Lut(ids_slot, self.param(lut), mv=mv)

        if p.type in (PredicateType.LIKE, PredicateType.REGEXP_LIKE):
            pattern = p.values[0]
            regex = like_to_regex(pattern) if p.type == PredicateType.LIKE else re.compile(str(pattern))
            lut = np.zeros(card + 1, dtype=bool)
            for i, v in enumerate(d.values):
                if regex.search(str(v)) is not None:
                    lut[i] = True
            return ir.Lut(ids_slot, self.param(lut), mv=mv)

        raise UnsupportedQueryError(f"predicate {p.type} not lowered")

    def _lower_host_mask(self, p: Predicate) -> ir.FilterNode:
        """Index-backed predicates without a vector form (JSON_MATCH /
        TEXT_MATCH / VECTOR_SIMILARITY) evaluate on host via their index
        into a doc mask shipped as a kernel param plane."""
        from .host_executor import eval_host_mask

        if not p.lhs.is_identifier:
            raise UnsupportedQueryError(f"{p.type} needs a column lhs")
        return self._mask_param(eval_host_mask(p, self.segment))

    def _mask_param(self, mask: np.ndarray) -> ir.MaskParam:
        """Host-computed doc mask → padded boolean param plane."""
        padded = np.zeros(self.padded_rows(), dtype=bool)
        padded[: len(mask)] = mask
        return ir.MaskParam(self.param(padded))

    def _and_valid_docs(self, filt: Optional[ir.FilterNode]) -> Optional[ir.FilterNode]:
        """Upsert tables AND the segment's validity plane into the fused
        filter (reference: FilterPlanNode wraps the filter with the
        validDocIds bitmap for upsert-enabled tables); shipped as a param
        plane so the compiled program is reused as validity evolves."""
        vd = getattr(self.segment, "valid_doc_ids", None)
        if vd is None:
            return filt
        node = self._mask_param(vd.mask(self.segment.num_docs))
        return node if filt is None else ir.FAnd((filt, node))

    def _id_interval(self, ids_slot, lo_id, hi_id, mv, card) -> ir.FilterNode:
        if mv:
            lut = np.zeros(card + 1, dtype=bool)
            lut[lo_id : hi_id + 1] = True
            return ir.Lut(ids_slot, self.param(lut), mv=True)
        return ir.Interval(
            ir.IdsCol(ids_slot),
            lo_param=self.param(np.int32(lo_id)),
            hi_param=self.param(np.int32(hi_id)),
        )

    def _lower_value_predicate(self, p: Predicate) -> ir.FilterNode:
        ve = self.value_expr(p.lhs)
        if p.type in (PredicateType.EQ, PredicateType.NOT_EQ):
            v = p.values[0]
            if isinstance(v, bool):
                v = int(v)
            if isinstance(v, str):
                raise UnsupportedQueryError("string compare on raw column")
            pi = self.param(np.float64(v) if isinstance(v, float) else np.int64(v))
            node = ir.Interval(ve, lo_param=pi, hi_param=pi)
            return ir.FNot(node) if p.type == PredicateType.NOT_EQ else node
        if p.type == PredicateType.RANGE:
            lo = None if p.lower is None else self.param(_num(p.lower))
            hi = None if p.upper is None else self.param(_num(p.upper))
            return ir.Interval(ve, lo_param=lo, hi_param=hi,
                               lo_inclusive=p.lower_inclusive, hi_inclusive=p.upper_inclusive)
        if p.type in (PredicateType.IN, PredicateType.NOT_IN):
            vals = np.asarray([_num(v) for v in p.values])
            node = ir.Isin(ve, self.param(vals))
            return ir.FNot(node) if p.type == PredicateType.NOT_IN else node
        raise UnsupportedQueryError(f"predicate {p.type} on raw column not lowered")

    def _sorted_table_rule(self, group_dims, num_groups, any_derived,
                           mv_group_slot, lowered):
        """(sorted, reason) for a group-by the dense table could hold. Every
        test here is about the SCAN: identifier keys over single-value
        dictionary columns (any number of them, any dictionary type: the
        kernel sorts the composite id Σ id_i · stride_i) with every
        aggregation a columnar count/sum/min/max get a sorted table from
        the first size the limb kernel leaves (mxu_groupby.MAX_GROUPS
        slots): above it the dense table is filled by one 32-bit scatter
        per limb over every row, whatever its size, and the chip sweeps
        (tools/groupby_crossover_sweep.py, PERF.md section 6, PR 30 for one
        key and PR 35 for composites) found the sort-based kernel ahead
        from there on at every filter factor, so the crossover is no
        constant of its own. Matrix ops, derived and MV keys stay dense:
        the sorted kernel refuses them. Which of the sorted tables the
        server then merges on the device (one key, an integer dictionary)
        is query_executor._device_merge_takes' own test; the others run
        the per-segment stages, as the dense table did."""
        if mxu_groupby.supports(num_groups + 1, 1):
            return False, (f"{num_groups} keys fit the limb kernel's "
                           f"{mxu_groupby.MAX_GROUPS} slots")
        if any_derived:
            return False, "a derived key"
        if mv_group_slot is not None:
            return False, "a multi-value key"
        for op in self.ops:
            if op.kind not in _SPARSE_AGG_KINDS:
                return False, f"{op.kind} needs the dense table"
        if not self.ops or any(la.vec is None for la in lowered):
            return False, "an aggregation without a columnar state"
        above = f"above the limb kernel's {mxu_groupby.MAX_GROUPS} slots"
        if len(group_dims) == 1:
            return True, f"one key of {num_groups} entries, {above}"
        keys = " x ".join(f"{d.column}[{d.cardinality}]" for d in group_dims)
        return True, f"{len(group_dims)} keys {keys} = {num_groups}, {above}"

    def _exact_sums_stay(self, program: ir.Program, lowered) -> None:
        """SUMPRECISION's lowering (`aggregation._exact_sum_op`) proves a
        segment's total fits int64; this keeps it where the kernel forms
        that total as an integer: the ungrouped sum, and a dense table's
        limb pass or 32-bit limb scatters (a value past int32 only on the
        limb pass, with room for its digits: `kernels.dense_sum_limbs`,
        every unbounded SUM before it counted as an int32 plane, so never
        more room than the kernel finds). A sorted table's sums are
        float64 prefix differences, a dense scatter of a wider value a
        float64 scatter, and the TPU carries a float64 in two float32
        words (exact to about 2^48); a multi-value key counts a row once a
        value: the host engine sums each of these."""
        from ..ops.kernels import dense_sum_limbs, group_table_form

        exact = [(la, self.ops[la.vec.outs[0] - 1]) for la in lowered
                 if la.semantics.result_type == "BIG_DECIMAL"]
        if not exact or program.mode == "aggregation":
            return
        table = group_table_form(program)
        if program.mode != "group_by" or program.mv_group_slot is not None:
            raise UnsupportedQueryError(
                f"{exact[0][0].name} on a {table} group table"
                + (" with a multi-value key" if program.mv_group_slot
                   is not None else ": its sums are float64 there") +
                ", so the host engine sums it exactly")
        limbs, _ = dense_sum_limbs(program, self.padded_rows(),
                                   lambda agg: True)
        taken = {op for op, lb in zip(program.aggs, limbs) if lb is not None}
        for la, op in exact:
            if op not in taken and not (
                    -(1 << 31) <= op.vmin and op.vmax < (1 << 31)):
                raise UnsupportedQueryError(
                    f"{la.name} past int32 on a {table} group table: no "
                    "limb pass holds its int64 digits there, so the host "
                    "engine sums it exactly")

    # -- top-level plan ----------------------------------------------------
    def plan(self) -> SegmentPlan:
        q = self.query
        filt = self.lower_filter(q.filter)
        filt = self._and_valid_docs(filt)

        if q.is_aggregation_query or q.distinct or q.is_group_by:
            group_dims: list[GroupDim] = []
            group_exprs = list(q.group_by_expressions)
            if q.distinct and not q.is_aggregation_query:
                group_exprs = [e for e in q.select_expressions]
            group_slots = []
            group_vexprs = []
            cards = []
            any_derived = False
            mv_group_slot = mv_group_card = None
            for ge in group_exprs:
                if ge.is_identifier:
                    info = self.dict_info(ge)
                    if info is None:
                        raise UnsupportedQueryError(f"group-by on non-dict column {ge}")
                    m = self._meta(ge.identifier)
                    slot, card, d = info
                    if not m.single_value:
                        # ONE MV dim: the kernel expands (doc × mv-slot)
                        # pairs; a second would need a per-doc cross
                        # product (host path handles it)
                        if mv_group_slot is not None:
                            raise UnsupportedQueryError(
                                "group-by on two MV columns needs host path")
                        mv_group_slot, mv_group_card = slot, card
                    group_slots.append(slot)
                    group_vexprs.append(ir.IdsCol(slot))
                    cards.append(card)
                    group_dims.append(GroupDim(ge.identifier, card, d))
                else:
                    derived = self._derived_dim(ge)
                    if derived is None:
                        raise UnsupportedQueryError(f"group-by on expression {ge} needs host path")
                    vexpr, card, dd = derived
                    any_derived = True
                    group_vexprs.append(vexpr)
                    cards.append(card)
                    group_dims.append(GroupDim(str(ge), card, dd))
            num_groups = 1
            for c in cards:
                num_groups *= c
            if num_groups >= SPARSE_KEY_LIMIT:
                raise UnsupportedQueryError(
                    f"group cardinality product {num_groups} exceeds the "
                    "int64 composite-key space")
            strides = row_major_strides(cards)

            # lets approximate aggs size their occupancy matrices: e.g. the
            # tdigest family picks exact value-hist vs fixed-bin by whether
            # groups × dict-card fits the dense table
            self.group_card_hint = num_groups
            lowered = [lower_aggregation(self, a) for a in q.aggregations]
            if mv_group_slot is not None:
                if any_derived:
                    raise UnsupportedQueryError(
                        "MV group-by with expression keys needs host path")
                # expansion rewires every 1-D plane: aggs referencing MV
                # matrices (another MV column, or MvLutReduce of this one)
                # would see the wrong shape — host path handles the combo
                mv_slots = {i for i, (_c, k) in enumerate(self._slots)
                            if k == "mvids"}
                for op in self.ops:
                    if (op.ids_slot in mv_slots
                            or _vexpr_uses_slots(op.vexpr, mv_slots)):
                        raise UnsupportedQueryError(
                            "MV aggregation with MV group-by needs host path")
            # mode selection: dense when the key product AND every matrix
            # occupancy fit the segment_sum table; otherwise the sort-based
            # sparse path when every op supports it (scalar reductions +
            # distinct via pair dedup); otherwise host
            dense_ok = num_groups <= DENSE_GROUP_LIMIT
            dense_reason = f"group cardinality product {num_groups}"
            for op in self.ops:
                width = op.card if op.kind in ("distinct_bitmap", "value_hist") else (
                    op.bins if op.kind in ("hist_fixed", "hist_adaptive")
                    else None)
                if width is not None and num_groups * width > DENSE_GROUP_LIMIT:
                    dense_ok = False
                    dense_reason = f"{op.kind} occupancy {num_groups}x{width}"
            sparse = not dense_ok
            # a table sorted BY THE RULE holds every key of the dictionary
            # (every combination of several keys): nothing is trimmed in a
            # segment, as in the dense table it replaces (numGroupsLimit
            # keeps its meaning for the other two)
            whole_table = False
            if not sparse and group_exprs and self.query.query_options.get(
                    "sparseGroupBy") in (True, "true", 1):
                # per-query escape hatch (SET sparseGroupBy = true): route a
                # dense-eligible group-by through the sparse kernel — lets
                # tests and benchmarks exercise the sort/presorted/device-
                # combine machinery without multi-million-cardinality data
                sparse = True
                dense_reason = "sparseGroupBy=true"
            elif not sparse and group_exprs:
                whole_table, dense_reason = self._sorted_table_rule(
                    group_dims, num_groups, any_derived, mv_group_slot,
                    lowered)
                sparse = whole_table
            if sparse:
                n_distinct = sum(1 for op in self.ops
                                 if op.kind == "distinct_bitmap")
                if n_distinct > 1:
                    # one DISTINCT column rides the sort as the secondary
                    # key; a second would need its own n-length sort
                    raise UnsupportedQueryError(
                        "sparse group-by supports one DISTINCT column "
                        "(host path handles more)")
                for op in self.ops:
                    if op.kind == "distinct_bitmap":
                        # the sparse kernel ships per-slot dict-id bitmaps
                        # (ceil(card/32) words/slot) — bound the width
                        if op.card > 1024:
                            raise UnsupportedQueryError(
                                f"sparse DISTINCTCOUNT bitmap over card "
                                f"{op.card} > 1024 runs on the host engine")
                        continue
                    if op.kind not in _SPARSE_AGG_KINDS:
                        raise UnsupportedQueryError(
                            f"{dense_reason} exceeds the dense limit and "
                            f"{op.kind} is unsupported in sparse "
                            "(sort-based) group-by")
            if sparse and not group_exprs:
                # un-grouped aggregation with an oversized occupancy matrix
                # (e.g. DISTINCTCOUNT of a multi-million-card column): the
                # sort kernel needs group keys; host handles this shape
                raise UnsupportedQueryError(
                    f"{dense_reason} exceeds the dense limit for an "
                    "un-grouped aggregation")
            exact_trim = False
            keys_presorted = False
            if (sparse and group_exprs and not any_derived
                    and mv_group_slot is None
                    and all(e.is_identifier for e in group_exprs)):
                # sorted-key fast path: group keys whose COMPOSITE id
                # Σ id_i·stride_i is nondecreasing in doc order need NO
                # sort at all; the kernel reads group edges off the id
                # planes (reference SortedGroupByOperator).
                #   single key  — the column's own dict-id plane is
                #     nondecreasing (sorted ingestion, ColumnMetadata
                #     .is_sorted);
                #   composite — the keys are, IN ORDER, a prefix of the
                #     segment's lexicographic co-sort chain
                #     (SegmentMetadata.sort_order: leading key globally
                #     sorted, later keys sorted within runs of the
                #     prefix). Row-major strides make lexicographic
                #     nondecreasing ids ⇒ nondecreasing composite.
                metas = [self._meta(e.identifier) for e in group_exprs]
                if all(m.single_value for m in metas):
                    if len(group_exprs) == 1:
                        keys_presorted = bool(
                            getattr(metas[0], "is_sorted", False))
                    else:
                        so = list(getattr(
                            getattr(self.segment, "metadata", None),
                            "sort_order", None) or [])
                        cols = [e.identifier for e in group_exprs]
                        keys_presorted = so[:len(cols)] == cols
            if sparse and group_exprs:
                # output capacity = numGroupsLimit: groups beyond it are
                # trimmed on device (reference InstancePlanMakerImplV2:245-270)
                capacity = table_bucket(num_groups) if whole_table \
                    else num_groups
                limit = capacity if whole_table else int(q.query_options.get(
                    "numGroupsLimit", DEFAULT_NUM_GROUPS_LIMIT))
                # ORDER-BY pushdown: when the query orders by an ASC prefix
                # of the group keys, the kernel's keep-smallest-L trim is
                # EXACT (sorted dictionaries make composite order =
                # lexicographic value order, and a segment's L smallest keys
                # contain every globally-L-smallest key it holds) — the
                # device then ships L slots instead of millions (reference:
                # ordering-aware server trim, TableResizer/minServerGroupTrimSize).
                # The table keeps OFFSET + LIMIT rounded up to a power of two
                # (still exact: the broker cuts to the LIMIT), so statements
                # that differ in their LIMIT or OFFSET share one program
                trim = None if any_derived else _orderby_prefix_trim(q)
                if trim is not None and trim <= limit:
                    limit = min(limit, _key_space_bucket(trim))
                    exact_trim = True
                mode = "group_by_sparse"
                out_groups = min(capacity, max(1, limit))
                if out_groups > SPARSE_GROUPS_LIMIT:
                    # bound device output allocation the same way the dense
                    # path bounds its table
                    raise UnsupportedQueryError(
                        f"numGroupsLimit {out_groups} exceeds sparse output "
                        f"cap {SPARSE_GROUPS_LIMIT}")
            else:
                mode = "group_by" if group_exprs else "aggregation"
                out_groups = num_groups
            program = ir.Program(
                mode=mode,
                filter=filt,
                aggs=tuple(self.ops),
                group_slots=() if any_derived else tuple(group_slots),
                group_strides=tuple(strides),
                num_groups=out_groups,
                group_vexprs=tuple(group_vexprs) if any_derived else (),
                key_space=(_key_space_bucket(capacity)
                           if mode == "group_by_sparse" else 0),
                exact_trim=exact_trim,
                keys_presorted=(keys_presorted
                                and mode == "group_by_sparse"),
                mv_group_slot=mv_group_slot if mode != "aggregation" else None,
                mv_group_card=mv_group_card if mode != "aggregation" else None,
                mv_doc_slots=tuple(
                    i for i, (_c, k) in enumerate(self._slots)
                    if k in ("ids", "raw", "rawf32r", "null"))
                if mv_group_slot is not None else (),
            )
            self._exact_sums_stay(program, lowered)
            return SegmentPlan(program, self._slots, self._params,
                               lowered, group_dims,
                               fused_ok=self._fused_ok(),
                               group_table_reason=dense_reason
                               if group_exprs else "")

        # selection: kernel computes the mask; host materializes rows.
        # Transform select/order expressions evaluate host-side over the
        # already-filtered doc ids only — the device's job here is the filter.
        from .selection import selection_columns_for

        sel_cols, sel_exprs = selection_columns_for(q, self.segment)
        for c in sel_cols:
            if c not in sel_exprs:
                self._meta(c)
        program = ir.Program(mode="selection", filter=filt)
        return SegmentPlan(program, self._slots, self._params,
                           selection_columns=sel_cols, selection_exprs=sel_exprs)


_BIN_FN = {
    "plus": "add", "minus": "sub", "times": "mul", "divide": "div", "mod": "mod",
    "pow": "pow", "power": "pow",
    "equals": "eq", "notequals": "ne", "lessthan": "lt", "lessthanorequal": "le",
    "greaterthan": "gt", "greaterthanorequal": "ge",
    "and": "and", "or": "or", "least": "min", "greatest": "max",
}

_UN_FN = {
    "neg": "neg", "abs": "abs", "not": "not", "exp": "exp", "ln": "ln",
    "log10": "log10", "log2": "log2", "sqrt": "sqrt", "ceiling": "ceil",
    "ceil": "ceil", "floor": "floor", "sign": "sign",
}


def _num(v):
    if isinstance(v, bool):
        return np.int64(int(v))
    if isinstance(v, int):
        return np.int64(v)
    if isinstance(v, float):
        return np.float64(v)
    raise UnsupportedQueryError(f"non-numeric literal {v!r} on raw column")


def like_to_regex(pattern: str):
    """SQL LIKE → compiled regex (reference RegexpPatternConverterUtils:
    % → .*, _ → ., everything else escaped)."""
    out = []
    for ch in str(pattern):
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return re.compile("^" + "".join(out) + "$")
