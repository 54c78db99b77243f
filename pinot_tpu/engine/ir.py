"""Kernel program IR — the static shape of a per-segment query kernel.

This is the TPU build's replacement for the reference's operator tree
(pinot-core/.../plan/ — GroupByPlanNode/AggregationPlanNode/SelectionPlanNode
over Operator.nextBlock pull loops). Instead of virtual-call operators pulling
10K-doc blocks, a query compiles to a *Program*: a small frozen (hashable)
tree interpreted once inside `jax.jit` (ops/kernels.py:run_program). Because
the Program is a static jit argument, all literal values live in the runtime
`params` tuple — structurally identical queries over same-shaped segments hit
the XLA compile cache regardless of literals.

Slot model: `arrays[i]` are device-resident column planes (dict-id planes,
raw value planes, numeric dictionaries, null bitmaps); `params[i]` are
per-query values (interval bounds, LUTs, IN-lists). The planner
(engine/plan.py) assigns slots.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Optional

# Sparse group-by composite keys must stay strictly below this value: the
# kernel uses it as the masked-row sort sentinel (rows with key >= sentinel
# are treated as filtered out), and the planner rejects cardinality products
# reaching it. One constant, imported by both sides, so the invariant can't
# drift (ops/kernels._run_sparse_group_by, engine/plan.SegmentPlanner.plan).
SPARSE_KEY_SPACE = 1 << 62

# ---------------------------------------------------------------------------
# Value expressions (→ reference TransformFunction,
# pinot-core/.../operator/transform/function/TransformFunction.java:35)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ValueExpr:
    pass


@dataclass(frozen=True)
class Col(ValueExpr):
    """A raw value plane already on device."""

    slot: int


@dataclass(frozen=True)
class DictGather(ValueExpr):
    """dictionary[dict_ids] — numeric dict decode on device."""

    ids_slot: int
    dict_slot: int


@dataclass(frozen=True)
class IdsCol(ValueExpr):
    """The dict-id plane itself (used for group keys / dict-space compares)."""

    slot: int


@dataclass(frozen=True)
class ConstParam(ValueExpr):
    """Scalar literal passed at runtime (params[idx])."""

    idx: int


@dataclass(frozen=True)
class ParamGather(ValueExpr):
    """params[param_idx][ids] — a host-computed lookup table gathered on
    device. The planner uses this for dictionary transforms: a string/complex
    transform function is evaluated ONCE over the column's dictionary on host
    (cardinality values, not num_docs), and the per-row result becomes a
    single gather — the TPU analogue of the reference evaluating dictionary-
    based transforms per 10K-doc block."""

    ids: ValueExpr  # int plane (IdsCol or another ParamGather for remaps)
    param_idx: int


@dataclass(frozen=True)
class Bin(ValueExpr):
    op: str  # add sub mul div fdiv mod pow eq ne lt le gt ge and or min max
    a: ValueExpr
    b: ValueExpr


@dataclass(frozen=True)
class Un(ValueExpr):
    op: str  # neg abs not exp ln log10 log2 sqrt ceil floor sign
    a: ValueExpr


@dataclass(frozen=True)
class Cast(ValueExpr):
    a: ValueExpr
    to: str  # INT LONG FLOAT DOUBLE BOOLEAN


@dataclass(frozen=True)
class Where(ValueExpr):
    cond: ValueExpr
    a: ValueExpr
    b: ValueExpr


@dataclass(frozen=True)
class FilterVal(ValueExpr):
    """A lowered FILTER subtree used as a boolean VALUE plane — the bridge
    that lets FILTER (WHERE ...) clause conditions reuse the whole
    predicate lowering (dict-id LUTs, intervals, host index masks) inside
    an aggregation operand wrap. Declared after FilterNode; the field is
    typed loosely to avoid a forward reference."""

    filter: object  # FilterNode


@dataclass(frozen=True)
class NullCol(ValueExpr):
    """The column's null bitmap plane as a boolean value (advanced null
    handling: agg operands wrap as Where(NullCol, identity, v) so null
    rows contribute the op identity — reference
    QueryContext.isNullHandlingEnabled semantics)."""

    null_slot: int


@dataclass(frozen=True)
class MvLutReduce(ValueExpr):
    """Per-doc reduce of an MV column: params[lut_param][mv_ids] is a
    (docs, max_mv) value matrix whose pad-sentinel slot (index card) holds
    the op identity, row-reduced to one value per doc. op="count" needs no
    LUT at all — it counts non-sentinel slots (lut_param None, card set).
    Lowers SUMMV / COUNTMV / MINMV / MAXMV / AVGMV onto the standard
    scalar agg kernels (reference SumMVAggregationFunction et al., which
    loop per-doc value arrays — here the ragged column is a rectangular
    matrix and the reduce is one fused device op)."""

    ids_slot: int
    lut_param: Optional[int]
    op: str  # sum | min | max | count
    card: Optional[int] = None  # count: the pad sentinel id


# ---------------------------------------------------------------------------
# Filter nodes (→ reference BaseFilterOperator tree,
# pinot-core/.../operator/filter/; predicates become vector compares)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FilterNode:
    pass


@dataclass(frozen=True)
class FConst(FilterNode):
    value: bool


@dataclass(frozen=True)
class Interval(FilterNode):
    """lo <= v <= hi with optional open bounds; params hold the bounds.

    Dict-encoded predicates are normalized on host to a dict-id interval
    (sorted dictionaries make value ranges id ranges); raw predicates compare
    in value space.
    """

    vexpr: ValueExpr
    lo_param: Optional[int] = None
    hi_param: Optional[int] = None
    lo_inclusive: bool = True
    hi_inclusive: bool = True


@dataclass(frozen=True)
class Lut(FilterNode):
    """mask = lut[dict_ids] — arbitrary dictionary predicate (IN, LIKE, REGEXP,
    NOT_IN...) evaluated against the dictionary on host into a boolean LUT.
    MV-safe: LUT is sized cardinality+1 with the pad sentinel false."""

    ids_slot: int
    lut_param: int
    mv: bool = False


@dataclass(frozen=True)
class Isin(FilterNode):
    """Raw-column IN: compare against a small padded value array
    (pad = repeat of first value, harmless for membership)."""

    vexpr: ValueExpr
    values_param: int


@dataclass(frozen=True)
class Null(FilterNode):
    """mask = null bitmap plane (IS_NULL)."""

    null_slot: int


@dataclass(frozen=True)
class MaskParam(FilterNode):
    """mask = params[idx] — a boolean doc plane evaluated on HOST at plan
    time (JSON_MATCH / TEXT_MATCH posting lists, precomputed index masks),
    padded to the segment's shape bucket before dispatch."""

    idx: int


@dataclass(frozen=True)
class FAnd(FilterNode):
    children: tuple[FilterNode, ...]


@dataclass(frozen=True)
class FOr(FilterNode):
    children: tuple[FilterNode, ...]


@dataclass(frozen=True)
class FNot(FilterNode):
    child: FilterNode


# ---------------------------------------------------------------------------
# Aggregation ops (primitive device reductions; SQL agg functions lower to
# one or more of these — engine/aggregation.py)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AggOp:
    kind: str  # count | sum | min | max | sumsq | distinct_bitmap | value_hist | hist_fixed | hist_adaptive
    vexpr: Optional[ValueExpr] = None
    # distinct_bitmap / value_hist: dict-id plane slot + static cardinality
    ids_slot: Optional[int] = None
    card: Optional[int] = None
    # hist_fixed / hist_adaptive: static bin count + runtime [lo, hi] bounds
    bins: Optional[int] = None
    lo_param: Optional[int] = None
    hi_param: Optional[int] = None
    # hist_adaptive: the target percentile (static) — level-2 bins refine
    # each group's coarse bucket containing this quantile
    pct: Optional[float] = None
    # static integer value bounds when the planner knows them (column
    # metadata / dictionary min-max) — lets integer sums skip limbs and the
    # negative-count pass in the exact i32-scatter decomposition
    vmin: Optional[int] = None
    vmax: Optional[int] = None
    # hist_adaptive over a raw float column: vexpr evaluates to a PRE-REBASED
    # f32 offset plane ((v - column_min) stored f32 in HBM — half the read
    # bandwidth of the f64 plane and no per-row f64 subtract; the TPU has no
    # f64 ALU). lo_param still carries the f64 base for host-side decode.
    prebased: bool = False


# ---------------------------------------------------------------------------
# Program
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Program:
    mode: str  # "group_by" | "group_by_sparse" | "aggregation" | "selection"
    filter: Optional[FilterNode]
    aggs: tuple[AggOp, ...] = ()
    # group-by: per-dim dict-id plane slots + cartesian strides
    # (reference DictionaryBasedGroupKeyGenerator cartesian-product int keys,
    # pinot-core/.../groupby/DictionaryBasedGroupKeyGenerator.java:119-137).
    # Dense mode materializes a (num_groups+1,) table per agg; sparse mode
    # (cardinality product beyond the dense HBM limit) sorts 64-bit composite
    # keys on device and emits at most num_groups = numGroupsLimit groups —
    # the device analogue of the reference's hash-map key generators with
    # numGroupsLimit trim (InstancePlanMakerImplV2.java:245-270). Sparse
    # kernels append a (num_groups,) int64 key plane as the LAST output.
    group_slots: tuple[int, ...] = ()
    group_strides: tuple[int, ...] = ()
    num_groups: int = 1
    # expression group keys (derived dimensions): per-dim int ValueExprs,
    # same strides. Used when a group-by key is a transform of a dict column
    # (ids remapped through a host-computed LUT — ParamGather). When set,
    # group_slots is empty.
    group_vexprs: tuple[ValueExpr, ...] = ()
    # sparse mode: an upper bound on the FULL composite key space
    # (cardinality product before the numGroupsLimit cap, bucketed by
    # plan._key_space_bucket). Static, so the kernel can sort 32-bit keys
    # when they fit — 64-bit sorts and scatters are emulated on TPU
    key_space: int = 0
    # sparse mode: the device trim is an ORDER BY pushdown (ASC group-key
    # prefix + LIMIT) — result is exact, so don't flag numGroupsLimitReached
    exact_trim: bool = False
    # sparse mode: the SINGLE group key is a dict column whose id plane is
    # nondecreasing over the segment (ColumnMetadata.is_sorted — sorted
    # ingestion order, e.g. an order-key or time column). The kernel then
    # never sorts the rows by key: group runs are already contiguous, so edges
    # come straight from transitions in the raw id plane (the reference's
    # SortedGroupByOperator analogue).
    keys_presorted: bool = False
    # MV group-by: ONE group dim may be a multi-value column. The kernel
    # expands (doc × mv-slot) pairs up front — every 1-D plane broadcasts
    # across the MV width, the MV id matrix flattens, non-entries mask off
    # — then the dense/sparse machinery runs unchanged on the pairs
    # (reference MVGroupKeyGenerator emits one group key per MV entry).
    # Group-by outputs gain ONE extra trailing (1,) int64: matched DOC
    # count (pair counts no longer equal docs scanned).
    mv_group_slot: Optional[int] = None
    mv_group_card: Optional[int] = None
    # slots holding per-DOC 1-D planes (ids/raw/null) that the expansion
    # must broadcast across the MV width — dictionary planes are
    # cardinality-sized and must pass through untouched
    mv_doc_slots: tuple = ()


def sparse_groupby_path(p: Program) -> str:
    """The sparse kernel variant a Program lowers to — mirrors the branch
    taken by ops/kernels._run_sparse_group_by so EXPLAIN IMPLEMENTATION can
    name it without tracing the kernel: `sparse-presorted` sorts no row by key,
    `sparse-sort+gather` sorts (key[, distinct_ids], iota32) and gathers the
    >=2 payload operands through the permutation, `sparse-sort` carries a
    single payload through the sort network directly."""
    if p.keys_presorted:
        return "sparse-presorted"
    payloads = sum(1 for a in p.aggs
                   if a.kind in ("sum", "sumsq", "min", "max"))
    return "sparse-sort+gather" if payloads >= 2 else "sparse-sort"


def dict_gathers(p: Program) -> tuple:
    """Every DictGather of a Program (filter, group keys, aggregations), one
    entry per occurrence in the tree."""
    return _nodes_of(p, DictGather)


def luts(p: Program) -> tuple:
    """Every Lut of a Program (its filter and the filters inside values),
    one entry per occurrence in the tree."""
    return _nodes_of(p, Lut)


def _nodes_of(p: Program, kind: type) -> tuple:
    found = []

    def walk(x):
        if isinstance(x, kind):
            found.append(x)
        elif isinstance(x, tuple):
            for c in x:
                walk(c)
        elif isinstance(x, (ValueExpr, FilterNode, AggOp, Program)):
            for f in fields(x):
                walk(getattr(x, f.name))

    walk(p)
    return tuple(found)


# ---------------------------------------------------------------------------
# Program label: the stable device-side name of a query shape
# ---------------------------------------------------------------------------

_MODE_TAGS = {"selection": "sel", "aggregation": "agg", "group_by": "gby",
              "group_by_sparse": "gbs"}
# a label is part of an XLA module's name and of every profiler event of
# the module: long enough to tell SSB's shapes apart, short enough to read
_LABEL_MAX = 96


def _value_tokens(node, out: list) -> None:
    if isinstance(node, Col):
        out.append(f"c{node.slot}")
    elif isinstance(node, IdsCol):
        out.append(f"i{node.slot}")
    elif isinstance(node, DictGather):
        out.append(f"d{node.ids_slot}")
    elif isinstance(node, ConstParam):
        out.append("p")
    elif isinstance(node, ParamGather):
        out.append("pg")
        _value_tokens(node.ids, out)
    elif isinstance(node, Bin):
        out.append(node.op)
        _value_tokens(node.a, out)
        _value_tokens(node.b, out)
    elif isinstance(node, Un):
        out.append(node.op)
        _value_tokens(node.a, out)
    elif isinstance(node, Cast):
        out.append("as" + node.to.lower())
        _value_tokens(node.a, out)
    elif isinstance(node, Where):
        out.append("if")
        _value_tokens(node.cond, out)
        _value_tokens(node.a, out)
        _value_tokens(node.b, out)
    elif isinstance(node, NullCol):
        out.append(f"n{node.null_slot}")
    elif isinstance(node, FilterVal):
        out.append("fv")
        _filter_tokens(node.filter, out)
    elif isinstance(node, MvLutReduce):
        out.append(f"mv{node.op}{node.ids_slot}")
    else:
        out.append("x")


def _filter_tokens(node, out: list) -> None:
    if isinstance(node, FConst):
        out.append("true" if node.value else "false")
    elif isinstance(node, Interval):
        lo = None if node.lo_param is None else \
            ("ge" if node.lo_inclusive else "gt")
        hi = None if node.hi_param is None else \
            ("le" if node.hi_inclusive else "lt")
        out.append("rng" if lo and hi else (lo or hi or "any"))
        _value_tokens(node.vexpr, out)
    elif isinstance(node, Lut):
        out.append(f"lut{node.ids_slot}")
    elif isinstance(node, Isin):
        out.append("in")
        _value_tokens(node.vexpr, out)
    elif isinstance(node, Null):
        out.append(f"null{node.null_slot}")
    elif isinstance(node, MaskParam):
        out.append("mask")
    elif isinstance(node, (FAnd, FOr)):
        out.append(("and" if isinstance(node, FAnd) else "or")
                   + str(len(node.children)))
        for c in node.children:
            _filter_tokens(c, out)
    elif isinstance(node, FNot):
        out.append("not")
        _filter_tokens(node.child, out)
    else:
        out.append("x")


_LABELS: dict = {}


def program_label(p: Program) -> str:
    """The name a Program's executables and profiler events carry on the
    device: its mode, the filter tree's operators in prefix order, the
    group keys and the aggregation functions, each with the SLOT it reads
    (`i0` a dict-id plane, `c2` a raw plane, `d1` a dictionary gather).
    Made from the Program alone and from nothing that varies between two
    runs of one SQL shape — no literal (they live in `params`), no
    cardinality, no hash — so two PRs' traces can be laid side by side.
    Two shapes may share a label (a label is cut at 96 characters; the
    executables stay one per compiled family either way).

        SELECT SUM(a * b) WHERE x = 1 AND y BETWEEN 2 AND 3 AND z < 4
        -> agg_and3_rng_i0_rng_i1_lt_c2_sum_mul_c3_d1
    """
    label = _LABELS.get(p)
    if label is not None:
        return label
    out = [_MODE_TAGS.get(p.mode, "prg")]
    if p.filter is not None:
        _filter_tokens(p.filter, out)
    if p.group_vexprs:
        out.append("by")
        for v in p.group_vexprs:
            _value_tokens(v, out)
    elif p.group_slots:
        out.append("by" + "x".join(str(s) for s in p.group_slots))
    if p.mv_group_slot is not None:
        out.append(f"mv{p.mv_group_slot}")
    if p.keys_presorted:
        out.append("presorted")
    for a in p.aggs:
        out.append(a.kind.replace("_", ""))
        if a.vexpr is not None:
            _value_tokens(a.vexpr, out)
        elif a.ids_slot is not None:
            out.append(f"i{a.ids_slot}")
    label = "_".join(out)
    if len(label) > _LABEL_MAX:
        label = label[:_LABEL_MAX - 4].rstrip("_") + "_etc"
    if len(_LABELS) < 4096:  # same bound as the compile-cache guard
        _LABELS[p] = label
    return label
