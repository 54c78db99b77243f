"""Aggregation functions: device lowering + host semantics.

Reference: pinot-core/.../query/aggregation/function/ (93 impls behind
AggregationFunction.aggregate/aggregateGroupBySV — .../AggregationFunction.java:74-82).
The TPU design splits each SQL aggregation into:
  1. *primitive device reductions* (AggOp: count/sum/min/max/sumsq/
     distinct_bitmap/value_hist/hist_fixed) fused into the segment kernel
     (ops/kernels.py),
  2. a host-side *intermediate state* per group (analogue of the reference's
     intermediate results shipped in DataTables),
  3. shared `AggSemantics` (merge across segments/servers + finalize at
     broker reduce + result type) used identically by the device path and
     the host (numpy) fallback engine, so the two paths can never drift.

Approximate functions (DISTINCTCOUNTHLL / THETA / PERCENTILETDIGEST / ...)
use the mergeable sketch states in utils/sketches.py — value-based, so they
merge across segments whose dictionaries differ.

Result types follow the reference (AggregationFunction.getFinalResultColumnType):
COUNT→LONG, SUM/MIN/MAX/AVG/PERCENTILE*→DOUBLE, DISTINCTCOUNT→INT,
DISTINCTCOUNTHLL/THETA→LONG.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from decimal import Decimal
from typing import Callable, Optional

import numpy as np

from ..query.expressions import ExpressionContext, FunctionContext
from ..utils.sketches import (
    HyperLogLog,
    SmartDistinctSet,
    TDigest,
    ThetaSketch,
    ValueHist,
)
from . import ir


class UnsupportedQueryError(Exception):
    """Raised when a query shape can't lower to the device kernel; callers
    fall back to the host (numpy) engine."""


@dataclass
class AggSemantics:
    """Cross-segment merge + broker finalize for one aggregation function."""

    merge: Callable  # (a, b) -> state
    finalize: Callable  # (state) -> python scalar
    result_type: str
    empty_value: object  # result when zero rows matched (aggregation query)


# One table of (merge spec, finalize tag) per scalar aggregation, shared by
# the device lowering (VecAgg below) and the host vectorized group-by
# (host_executor._group_by_vectorized) so their GroupArrays stay mergeable.
VEC_RECIPES = {
    "count": (("add",), ("id", 0)),
    "sum": (("add",), ("id", 0)),
    "min": (("min",), ("id", 0)),
    "max": (("max",), ("id", 0)),
    "avg": (("add", "add"), ("div", 0, 1)),
    "minmaxrange": (("min", "max"), ("sub", 1, 0)),
}


@dataclass
class VecAgg:
    """Columnar (vectorized) form of one aggregation for the GroupArrays
    fast path: extract pulls per-component numpy columns for ALL groups at
    once; spec gives each component's cross-segment merge op; fin_tag is a
    picklable finalize recipe evaluated by the broker reducer
    (("id", c) | ("div", a, b) | ("sub", a, b) over component indices);
    outs names, per component, the kernel output the component is read
    from (the device merge ranks a component where it stands)."""

    spec: tuple  # per component: "add" | "min" | "max"
    extract: Callable  # (outs, gids) -> tuple[np.ndarray, ...]
    fin_tag: tuple
    outs: tuple = ()  # per component: index into the kernel's outputs


@dataclass
class LoweredAgg:
    """Device lowering of one SQL aggregation: how to read kernel outputs.

    extract(outs, g) builds the per-group intermediate state from the kernel
    output tuple (outs[0] is always the per-group row count). vec, when set,
    is the whole-table columnar form (GroupArrays fast path).
    """

    name: str
    semantics: AggSemantics
    extract: Callable  # (outs, g) -> state
    vec: "VecAgg | None" = None
    # optional batch form: prepare(outs) -> (g -> state). The executor uses
    # it on the dict path so per-output work (e.g. decoding the sparse
    # distinct pair list) runs ONCE, vectorized, instead of per group.
    prepare: "Callable | None" = None


# ---------------------------------------------------------------------------
# Argument model: leading args are data expressions, the rest are literal
# parameters (reference: PERCENTILE(col, 95), HISTOGRAM(col, 0, 100, 10),
# FIRSTWITHTIME(dataCol, timeCol, 'dataType')...).
# ---------------------------------------------------------------------------

_DATA_ARITY = {
    "count": 1,
    "covarpop": 2,
    "covarsamp": 2,
    "corr": 2,
    "exprmin": 2,
    "exprmax": 2,
    "firstwithtime": 2,
    "lastwithtime": 2,
}

# legacy digit-suffixed percentiles: PERCENTILE95(col) ≡ PERCENTILE(col, 95)
# (shared pattern — query/expressions.py uses it for is_aggregation too)
from ..query.expressions import PERCENTILE_SUFFIX_RE as _PCT_SUFFIX  # noqa: E402
# cycle-safe: funnel.py imports this module only lazily
from .funnel import FUNNEL_FNS as _FUNNEL_FNS  # noqa: E402


def canonicalize(name: str, extra: tuple) -> tuple[str, tuple]:
    m = _PCT_SUFFIX.match(name)
    if m:
        base = m.group(1) + (m.group(3) or "")
        return base, (int(m.group(2)),) + extra
    return name, extra


def split_args(fn: FunctionContext):
    """→ (data_arg_expressions, literal_extras)."""
    arity = _DATA_ARITY.get(_PCT_SUFFIX.sub(lambda m: m.group(1), fn.name), 1)
    data = list(fn.arguments[:arity])
    extra = []
    for a in fn.arguments[arity:]:
        if not a.is_literal:
            raise UnsupportedQueryError(
                f"{fn.name}: parameter {a} must be a literal")
        extra.append(a.literal)
    return data, tuple(extra)


def semantics_for(expr: ExpressionContext) -> AggSemantics:
    fn = expr.function
    if fn.name == "filter":  # FILTER (WHERE ...) wrapper: inner semantics
        return semantics_for(fn.arguments[0])
    if fn.name in _FUNNEL_FNS:  # funnel args aren't (data, literal*)-shaped
        from .funnel import funnel_semantics

        return funnel_semantics(fn)
    _, extra = split_args(fn)
    return get_semantics(fn.name, extra)


def _pct(extra, default=50.0) -> float:
    return float(extra[0]) if extra else default


def _merge_maybe(pick):
    """Merge for states that may be None (empty groups/segments)."""

    def merge(a, b):
        if a is None:
            return b
        if b is None:
            return a
        return pick(a, b)

    return merge


def _var_finalize(name: str):
    def fin(state):
        n, s, sq = state
        if n == 0 or (name.endswith("samp") and n < 2):
            return math.nan
        var = sq / n - (s / n) ** 2
        if name.endswith("samp"):
            var = var * n / (n - 1)
        var = max(var, 0.0)
        return math.sqrt(var) if name.startswith("stddev") else var

    return fin


def _merge3(a, b):
    return (a[0] + b[0], a[1] + b[1], a[2] + b[2])


def _merge_tuple(a, b):
    return tuple(x + y for x, y in zip(a, b))


def _covar_finalize(name: str):
    def fin(state):
        n, sx, sy, sxy, sxx, syy = state
        if n == 0 or (name == "covarsamp" and n < 2):
            return math.nan
        cov = sxy / n - (sx / n) * (sy / n)
        if name == "covarsamp":
            return cov * n / (n - 1)
        if name == "corr":
            vx = sxx / n - (sx / n) ** 2
            vy = syy / n - (sy / n) ** 2
            denom = math.sqrt(max(vx, 0.0) * max(vy, 0.0))
            return cov / denom if denom else math.nan
        return cov

    return fin


def _moments_finalize(name: str):
    def fin(state):
        n, s1, s2, s3, s4 = state
        if n == 0:
            return math.nan
        mu = s1 / n
        m2 = s2 / n - mu * mu
        if m2 <= 0:
            return math.nan
        if name == "skewness":
            m3 = s3 / n - 3 * mu * s2 / n + 2 * mu**3
            return m3 / m2**1.5
        m4 = s4 / n - 4 * mu * s3 / n + 6 * mu * mu * s2 / n - 3 * mu**4
        return m4 / (m2 * m2) - 3.0

    return fin


_EXACT_DISTINCT = (
    "distinctcount", "distinctcountbitmap", "segmentpartitioneddistinctcount",
    "distinctcountmv", "distinctcountbitmapmv",
)
_HLL_FNS = ("distinctcounthll", "distinctcounthllplus", "distinctcountull",
            "distinctcountcpc", "distinctcounthllmv", "distinctcounthllplusmv")
_THETA_FNS = ("distinctcounttheta", "distinctcountrawtheta")
_PCT_EXACT = ("percentile", "percentilemv")
_PCT_DIGEST = ("percentileest", "percentiletdigest", "percentilekll",
               "percentilesmarttdigest", "percentileestmv", "percentiletdigestmv",
               "percentilekllmv", "percentilerawest", "percentilerawtdigest",
               "percentilerawkll")


def get_semantics(name: str, extra: tuple = ()) -> AggSemantics:
    name, extra = canonicalize(name, extra)
    if name in ("count", "countmv"):
        return AggSemantics(lambda a, b: a + b, lambda s: s, "LONG", 0)
    if name in ("sum", "summv"):
        return AggSemantics(lambda a, b: a + b, lambda s: s, "DOUBLE", 0.0)
    if name == "sumprecision":
        return AggSemantics(lambda a, b: a + b, str, "BIG_DECIMAL", "0")  # Decimal state
    if name in ("min", "minmv"):
        return AggSemantics(min, lambda s: s, "DOUBLE", math.inf)
    if name in ("max", "maxmv"):
        return AggSemantics(max, lambda s: s, "DOUBLE", -math.inf)
    if name in ("minmaxrange", "minmaxrangemv"):
        return AggSemantics(lambda a, b: (min(a[0], b[0]), max(a[1], b[1])),
                            lambda s: s[1] - s[0], "DOUBLE", -math.inf)
    if name in ("avg", "avgmv"):
        return AggSemantics(lambda a, b: (a[0] + b[0], a[1] + b[1]),
                            lambda s: (s[0] / s[1]) if s[1] else math.nan,
                            "DOUBLE", math.nan)
    if name in _EXACT_DISTINCT:
        return AggSemantics(lambda a, b: a | b, len, "INT", 0)
    if name == "distinctsum":
        return AggSemantics(lambda a, b: a | b, lambda s: float(sum(s)), "DOUBLE", 0.0)
    if name == "distinctavg":
        return AggSemantics(lambda a, b: a | b,
                            lambda s: sum(s) / len(s) if s else math.nan, "DOUBLE", math.nan)
    if name in _HLL_FNS:
        return AggSemantics(lambda a, b: a.merge(b), lambda s: s.cardinality(), "LONG", 0)
    if name in _THETA_FNS:
        return AggSemantics(lambda a, b: a.merge(b), lambda s: s.cardinality(), "LONG", 0)
    if name in ("distinctcountsmart", "distinctcountsmarthll"):
        return AggSemantics(lambda a, b: a.merge(b), lambda s: s.cardinality(), "INT", 0)
    if name in _PCT_EXACT:
        pct = _pct(extra)
        return AggSemantics(lambda a, b: a.merge(b),
                            lambda s, _p=pct: s.percentile(_p), "DOUBLE", math.nan)
    if name in _PCT_DIGEST:
        pct = _pct(extra)
        return AggSemantics(lambda a, b: a.merge(b),
                            lambda s, _p=pct: s.quantile(_p / 100.0), "DOUBLE", math.nan)
    if name == "mode":
        return AggSemantics(lambda a, b: a.merge(b), lambda s: s.mode(), "DOUBLE", math.nan)
    if name == "histogram":
        return AggSemantics(lambda a, b: a + b,
                            lambda s: [float(x) for x in s], "DOUBLE_ARRAY", [])
    if name in ("stddevpop", "stddevsamp", "varpop", "varsamp"):
        return AggSemantics(_merge3, _var_finalize(name), "DOUBLE", math.nan)
    if name in ("skewness", "kurtosis"):
        return AggSemantics(_merge_tuple, _moments_finalize(name), "DOUBLE", math.nan)
    if name in ("covarpop", "covarsamp", "corr"):
        return AggSemantics(_merge_tuple, _covar_finalize(name), "DOUBLE", math.nan)
    if name == "booland":
        # empty state is the AND identity (True) on both engines
        return AggSemantics(lambda a, b: a and b, bool, "BOOLEAN", True)
    if name in ("boolor", "boolagg"):
        return AggSemantics(lambda a, b: a or b, bool, "BOOLEAN", False)
    if name in ("exprmin", "firstwithtime"):
        return AggSemantics(_merge_maybe(lambda a, b: a if a[0] <= b[0] else b),
                            lambda s: None if s is None else s[1], "OBJECT", None)
    if name in ("exprmax", "lastwithtime"):
        return AggSemantics(_merge_maybe(lambda a, b: a if a[0] >= b[0] else b),
                            lambda s: None if s is None else s[1], "OBJECT", None)
    if name in ("arrayagg", "listagg"):
        distinct = len(extra) > 1 and bool(extra[1])
        dtype = str(extra[0]).upper() if extra else "DOUBLE"

        def fin(s, _d=distinct):
            vals = list(dict.fromkeys(s)) if _d else list(s)
            return vals

        return AggSemantics(lambda a, b: a + b, fin, f"{dtype}_ARRAY", [])
    raise UnsupportedQueryError(f"aggregation {name} not implemented")


# ---------------------------------------------------------------------------
# Device lowering
# ---------------------------------------------------------------------------


# dense occupancy ceiling shared with the planner's mode selection
# (plan.DENSE_GROUP_LIMIT aliases this)
DENSE_GROUP_LIMIT = 1 << 21


class AggPlanContext:
    """Planner callback surface used by lowerings to register device ops."""

    def __init__(self):
        self.ops: list[ir.AggOp] = []
        # group cardinality product, set by the planner before lowering —
        # approximate aggs use it to bound their occupancy matrices
        self.group_card_hint = 1

    def add_op(self, op: ir.AggOp) -> int:
        """Register a primitive op, dedup'd; returns its kernel output index
        (output 0 is the group count)."""
        if op in self.ops:
            return 1 + self.ops.index(op)
        self.ops.append(op)
        return len(self.ops)

    # provided by SegmentPlanner (engine/plan.py):
    def value_expr(self, e: ExpressionContext) -> ir.ValueExpr:  # pragma: no cover
        raise NotImplementedError

    def mv_reduce_expr(self, e: ExpressionContext, op: str):  # pragma: no cover
        """(vexpr, vmin, vmax) or None — planners without MV support fall
        back to host."""
        return None

    # advanced null handling hooks (SegmentPlanner overrides; the defaults
    # are the basic-mode behavior)
    null_handling = False

    def agg_operand(self, e: ExpressionContext, identity):
        return self.value_expr(e)

    def nonnull_count_op(self, e: ExpressionContext) -> int:
        return 0

    def _null_cond_for(self, e: ExpressionContext):
        return None

    def dict_info(self, e: ExpressionContext, sv_only: bool = False):  # pragma: no cover
        raise NotImplementedError

    def col_meta(self, e: ExpressionContext):
        """Column metadata for a plain identifier, else None (feeds
        storage-aware lowerings like the f32 shadow-plane histogram)."""
        return None

    def col_minmax(self, e: ExpressionContext):  # pragma: no cover
        raise NotImplementedError

    def param(self, value) -> int:  # pragma: no cover
        raise NotImplementedError


_HIST_BINS = 2048  # fixed-bin device histogram resolution for raw columns
# digest compression for histogram-fed device digests: squeezing 2048
# weighted bins into the default ~100 centroids compounds the binning
# error (observed 1.2% drift vs the host's value-fed digest)
_TDIGEST_COMPRESSION = 500


def _mul(a: ir.ValueExpr, b: ir.ValueExpr) -> ir.ValueExpr:
    return ir.Bin("mul", a, b)


def _lower_mv_value_agg(ctx: AggPlanContext, name: str, label: str,
                        sem: AggSemantics, arg: ExpressionContext) -> LoweredAgg:
    """SUMMV-family: the MV column row-reduces to one value per doc
    (ir.MvLutReduce), then rides the standard scalar agg kernels. Host
    semantics flatten all entries of matched docs — identical totals."""

    def op(kind: str) -> int:
        if ctx._null_cond_for(arg) is not None:
            raise UnsupportedQueryError(
                f"{name} over nullable {arg} with enableNullHandling "
                "runs on the host engine")
        r = ctx.mv_reduce_expr(arg, kind)
        if r is None:
            raise UnsupportedQueryError(
                f"{name} on {arg} has no device MV form (host path)")
        ve, vmin, vmax = r
        agg_kind = "sum" if kind in ("sum", "count") else kind
        return ctx.add_op(ir.AggOp(agg_kind, vexpr=ve, vmin=vmin, vmax=vmax))

    if name == "countmv":
        i = op("count")
        spec, tag = VEC_RECIPES["count"]
        return LoweredAgg(
            label, sem, lambda outs, g: int(outs[i][g]),
            vec=VecAgg(spec, lambda outs, gids: (outs[i][gids],), tag, (i,)))
    if name in ("summv", "minmv", "maxmv"):
        i = op(name[:-2])
        spec, tag = VEC_RECIPES[name[:-2]]
        return LoweredAgg(
            label, sem, lambda outs, g: float(outs[i][g]),
            vec=VecAgg(spec,
                       lambda outs, gids: (outs[i][gids].astype(float),), tag,
                       (i,)))
    if name == "minmaxrangemv":
        i_min, i_max = op("min"), op("max")
        spec, tag = VEC_RECIPES["minmaxrange"]
        return LoweredAgg(
            label, sem,
            lambda outs, g: (float(outs[i_min][g]), float(outs[i_max][g])),
            vec=VecAgg(spec,
                       lambda outs, gids: (outs[i_min][gids].astype(float),
                                           outs[i_max][gids].astype(float)),
                       tag, (i_min, i_max)))
    # avgmv: (sum of entries, COUNT OF ENTRIES — not docs)
    i_s, i_c = op("sum"), op("count")
    spec, tag = VEC_RECIPES["avg"]
    return LoweredAgg(
        label, sem,
        lambda outs, g: (float(outs[i_s][g]), int(outs[i_c][g])),
        vec=VecAgg(spec,
                   lambda outs, gids: (outs[i_s][gids].astype(float),
                                       outs[i_c][gids]), tag, (i_s, i_c)))


_FILTERABLE = frozenset(("count", "sum", "min", "max", "avg", "minmaxrange"))


def _count_op(ctx: AggPlanContext, arg, cond) -> int:
    """Kernel output index for a COUNT under null handling and/or a FILTER
    clause; 0 (the shared per-group doc count) when neither applies.
    add_op dedups, so COUNT(x) FILTER(c) and AVG(x) FILTER(c) share one
    op."""
    ncond = ctx._null_cond_for(arg) if arg is not None else None
    if cond is None and ncond is None:
        return 0
    one = ir.ConstParam(ctx.param(np.int32(1)))
    zero = ir.ConstParam(ctx.param(np.int32(0)))
    base = one if ncond is None else ir.Where(ncond, zero, one)
    ve = base if cond is None else ir.Where(cond, base, zero)
    return ctx.add_op(ir.AggOp("sum", vexpr=ve, vmin=0, vmax=1))


def _scalar_op(ctx: AggPlanContext, kind: str, arg, cond) -> int:
    """Kernel output index for a sum/min/max reduction over ``arg`` with
    null handling (agg_operand identity wrap) and an optional FILTER
    clause composed on top."""
    nullable = ctx._null_cond_for(arg) is not None
    if kind == "sum":
        bounds = _int_bounds(ctx, arg)
        if bounds and (nullable or cond is not None):
            # identity rows contribute 0
            bounds = {"vmin": min(0, bounds["vmin"]),
                      "vmax": max(0, bounds["vmax"])}
        ve = ctx.agg_operand(arg, 0)
        if cond is not None:
            ve = ir.Where(cond, ve, ir.ConstParam(ctx.param(np.int64(0))))
        return ctx.add_op(ir.AggOp("sum", vexpr=ve, **bounds))
    # min / max: identity rows need ±inf, so compare in f64
    ident_tok = "inf" if kind == "min" else "-inf"
    bounds = {} if (nullable or cond is not None) else _int_bounds(ctx, arg)
    ve = ctx.agg_operand(arg, ident_tok)
    if cond is not None:
        inf = np.inf if kind == "min" else -np.inf
        ve = ir.Where(cond, ir.Cast(ve, "DOUBLE"),
                      ir.ConstParam(ctx.param(np.float64(inf))))
    return ctx.add_op(ir.AggOp(kind, vexpr=ve, **bounds))


def lower_aggregation(ctx: AggPlanContext, expr: ExpressionContext,
                      _cond=None, _label=None) -> LoweredAgg:
    fn = expr.function
    if fn.name == "filter":
        # AGG(x) FILTER (WHERE cond) — reference
        # FilteredAggregationFunction: rows failing the clause contribute
        # the agg identity. The clause lowers through the PREDICATE path
        # (dict-id LUTs, intervals, index masks — and 3VL under null
        # handling), bridged into value space.
        inner, cond_expr = fn.arguments
        try:
            from ..query.converter import (FilterConversionError,
                                           filter_from_expression)

            cond = ir.FilterVal(ctx.lower_filter(
                filter_from_expression(cond_expr)))
        except (FilterConversionError, UnsupportedQueryError, AttributeError):
            cond = ctx.value_expr(cond_expr)  # boolean plane
            ncond = ctx._null_cond_for(cond_expr)
            if ncond is not None:  # 3VL: a null clause input is false
                cond = ir.Bin("and", cond, ir.Un("not", ncond))
        return lower_aggregation(ctx, inner, _cond=cond, _label=str(expr))
    raw_name, args = fn.name, fn.arguments
    label = _label or str(expr)
    data, extra = split_args(fn)
    name, extra = canonicalize(raw_name, extra)
    sem = get_semantics(name, extra)
    if _cond is not None and name not in _FILTERABLE:
        raise UnsupportedQueryError(
            f"FILTER clause over {name} has no device form (host path)")

    def cond_wrap(ve: ir.ValueExpr, ident: ir.ValueExpr) -> ir.ValueExpr:
        return ve if _cond is None else ir.Where(_cond, ve, ident)

    if name == "count":
        # advanced null handling counts non-null rows; a FILTER clause
        # counts clause-passing rows (composable)
        i = _count_op(ctx, data[0] if data else None, _cond)
        spec, tag = VEC_RECIPES["count"]
        return LoweredAgg(
            label, sem, lambda outs, g: int(outs[i][g]),
            vec=VecAgg(spec, lambda outs, gids: (outs[i][gids],), tag, (i,)))

    if name in ("sum", "min", "max"):
        i = _scalar_op(ctx, name, data[0], _cond)
        spec, tag = VEC_RECIPES[name]
        return LoweredAgg(
            label, sem, lambda outs, g: float(outs[i][g]),
            vec=VecAgg(spec,
                       lambda outs, gids, _i=i: (outs[_i][gids].astype(float),),
                       tag, (i,)))

    if name in ("countmv", "summv", "minmv", "maxmv", "avgmv", "minmaxrangemv"):
        return _lower_mv_value_agg(ctx, name, label, sem, data[0])

    if name == "minmaxrange":
        i_min = _scalar_op(ctx, "min", data[0], _cond)
        i_max = _scalar_op(ctx, "max", data[0], _cond)
        spec, tag = VEC_RECIPES["minmaxrange"]
        return LoweredAgg(
            label, sem,
            lambda outs, g: (float(outs[i_min][g]), float(outs[i_max][g])),
            vec=VecAgg(spec,
                       lambda outs, gids: (outs[i_min][gids].astype(float),
                                           outs[i_max][gids].astype(float)),
                       tag, (i_min, i_max)))

    if name == "avg":
        i = _scalar_op(ctx, "sum", data[0], _cond)
        # divide by the rows that CONTRIBUTED (non-null ∩ clause-passing)
        c = _count_op(ctx, data[0], _cond)
        spec, tag = VEC_RECIPES["avg"]
        return LoweredAgg(
            label, sem,
            lambda outs, g: (float(outs[i][g]), int(outs[c][g])),
            vec=VecAgg(spec,
                       lambda outs, gids, _i=i, _c=c: (
                           outs[_i][gids].astype(float), outs[_c][gids]),
                       tag, (i, c)))

    # branches below don't have device null-skipping forms; under advanced
    # null handling a nullable operand routes to the host engine (which
    # drops null rows before building states)
    for a in data:
        if ctx._null_cond_for(a) is not None:
            raise UnsupportedQueryError(
                f"{name} over nullable {a} with enableNullHandling "
                "runs on the host engine")

    if name in ("distinctcount", "distinctcountbitmap", "segmentpartitioneddistinctcount",
                "distinctsum", "distinctavg"):
        i, dictionary, card = _occupancy_op(ctx, data[0], name)
        numeric = name in ("distinctsum", "distinctavg")

        def state(ids, _d=dictionary, _numeric=numeric):
            sel = _d.values[ids]
            if _numeric:
                return frozenset(float(v) for v in sel)
            return frozenset(sel.tolist())

        def extract(outs, g, _i=i, _c=card, _state=state):
            return _state(_occ_ids(outs, _i, g, _c))

        return LoweredAgg(label, sem, extract,
                          prepare=_occ_prepare(i, card, state))

    if name in _HLL_FNS and not name.endswith("mv"):
        i, dictionary, card = _occupancy_op(ctx, data[0], name)
        log2m = int(extra[0]) if extra else 12

        def state(ids, _d=dictionary, _m=log2m):
            return HyperLogLog(_m).add_values(_d.values[ids])

        def extract(outs, g, _i=i, _c=card, _state=state):
            return _state(_occ_ids(outs, _i, g, _c))

        return LoweredAgg(label, sem, extract,
                          prepare=_occ_prepare(i, card, state))

    if name in _THETA_FNS:
        i, dictionary, card = _occupancy_op(ctx, data[0], name)

        def state(ids, _d=dictionary):
            return ThetaSketch().add_values(_d.values[ids])

        def extract(outs, g, _i=i, _c=card, _state=state):
            return _state(_occ_ids(outs, _i, g, _c))

        return LoweredAgg(label, sem, extract,
                          prepare=_occ_prepare(i, card, state))

    if name in ("distinctcountsmart", "distinctcountsmarthll"):
        i, dictionary, card = _occupancy_op(ctx, data[0], name)

        def state(ids, _d=dictionary):
            return SmartDistinctSet().add_values(_d.values[ids])

        def extract(outs, g, _i=i, _c=card, _state=state):
            return _state(_occ_ids(outs, _i, g, _c))

        return LoweredAgg(label, sem, extract,
                          prepare=_occ_prepare(i, card, state))

    if name in ("percentile", "mode"):
        i, dictionary = _value_hist_op(ctx, data[0], name)
        if not _numeric_dictionary(dictionary):
            raise UnsupportedQueryError(f"{name} requires a numeric column")

        def extract(outs, g, _i=i, _d=dictionary):
            row = outs[_i][g]
            nz = np.nonzero(row)[0]
            return ValueHist.from_arrays(_d.values[nz], row[nz])

        return LoweredAgg(label, sem, extract)

    if name in _PCT_DIGEST and not name.endswith("mv"):
        info = ctx.dict_info(data[0], sv_only=True)
        # exact value-hist only while groups × dict-card fits the dense
        # table; beyond it a high-card column (e.g. cent-rounded fares)
        # would otherwise reject the device path entirely. These are
        # APPROXIMATE functions by contract — the fixed-bin histogram's
        # quantile error ≤ (max-min)/bins stays inside the family's
        # tolerance (reference PercentileTDigestAggregationFunction is
        # itself a bounded-error sketch).
        if info is not None and _numeric_dictionary(info[2]) \
                and ctx.group_card_hint * info[1] <= DENSE_GROUP_LIMIT:
            i, dictionary = _value_hist_op(ctx, data[0], name)

            def extract(outs, g, _i=i, _d=dictionary):
                row = outs[_i][g]
                nz = np.nonzero(row)[0]
                return ValueHist.from_arrays(
                    _d.values[nz], row[nz]).to_tdigest(
                    compression=_TDIGEST_COMPRESSION)

            return LoweredAgg(label, sem, extract)
        # raw numeric column (or an occupancy-capped dict column)
        mm = ctx.col_minmax(data[0])
        if mm is None:
            raise UnsupportedQueryError(f"{name} needs numeric column stats")
        lo, hi = float(mm[0]), float(mm[1])
        if hi <= lo:
            hi = lo + 1.0
        pct = _pct(extra)

        from ..ops import mxu_groupby

        bins = min(64, max(1, (mxu_groupby.MAX_GROUPS - 1)
                           // max(1, ctx.group_card_hint)))
        if bins >= 8 and mxu_groupby.supports(
                ctx.group_card_hint * bins + 1, 1):
            # two-level adaptive device histogram (MXU count passes; see
            # kernels "hist_adaptive"): quantile resolution (hi-lo)/bins^2
            # concentrated around the asked percentile, 2*bins+1 output
            # words per group instead of _HIST_BINS.
            # Plain raw FLOAT/DOUBLE identifiers bin from a PRE-REBASED
            # f32 plane ((v - col_min) in HBM, half the f64 read
            # bandwidth); lo from col stats == the rebase base, so the
            # kernel's offsets line up exactly.
            vexpr = prebased = None
            e0 = data[0]
            m = ctx.col_meta(e0)
            if m is not None and m.encoding == "RAW" and m.single_value \
                    and str(m.data_type) in ("FLOAT", "DOUBLE"):
                vexpr = ir.Col(ctx.slot(e0.identifier, "rawf32r"))
                prebased = True
            if vexpr is None:
                # registering value_expr's raw/dict slots only on this
                # branch keeps the f64 plane OUT of the query's HBM
                # residency when the f32 shadow serves it alone
                vexpr, prebased = ctx.value_expr(data[0]), False
            i = ctx.add_op(ir.AggOp(
                "hist_adaptive", vexpr=vexpr, bins=bins,
                lo_param=ctx.param(np.float64(lo)),
                hi_param=ctx.param(np.float64(hi)), pct=float(pct),
                prebased=prebased))
            w1 = (hi - lo) / bins
            c1 = lo + (np.arange(bins) + 0.5) * w1

            def extract(outs, g, _i=i, _b=bins, _lo=lo, _w1=w1, _c1=c1):
                row = outs[_i][g]
                h1 = row[:_b].astype(np.float64)
                h2 = row[_b:2 * _b].astype(np.float64)
                bstar = int(row[2 * _b])
                # coarse weights minus the refined bucket, plus the
                # refined sub-bins centered inside it
                w = h1.copy()
                w[bstar] = 0.0
                lo_g = _lo + bstar * _w1
                c2 = lo_g + (np.arange(_b) + 0.5) * (_w1 / _b)
                d = TDigest(_TDIGEST_COMPRESSION).add_weighted(_c1, w)
                return d.add_weighted(c2, h2)

            return LoweredAgg(label, sem, extract)

        # fixed-bin device histogram → weighted t-digest
        i = ctx.add_op(ir.AggOp(
            "hist_fixed", vexpr=ctx.value_expr(data[0]), bins=_HIST_BINS,
            lo_param=ctx.param(np.float64(lo)), hi_param=ctx.param(np.float64(hi))))
        centers = lo + (np.arange(_HIST_BINS) + 0.5) * (hi - lo) / _HIST_BINS

        def extract(outs, g, _i=i, _c=centers):
            return TDigest(_TDIGEST_COMPRESSION).add_weighted(
                _c, outs[_i][g].astype(np.float64))

        return LoweredAgg(label, sem, extract)

    if name == "histogram":
        if len(extra) != 3:
            raise UnsupportedQueryError("histogram(col, lower, upper, numBins)")
        lo, hi, bins = float(extra[0]), float(extra[1]), int(extra[2])
        if hi <= lo or bins <= 0:
            raise UnsupportedQueryError("histogram requires upper > lower and numBins > 0")
        i = ctx.add_op(ir.AggOp(
            "hist_fixed", vexpr=ctx.value_expr(data[0]), bins=bins,
            lo_param=ctx.param(np.float64(lo)), hi_param=ctx.param(np.float64(hi))))
        return LoweredAgg(label, sem,
                          lambda outs, g: outs[i][g].astype(np.float64))

    if name in ("stddevpop", "stddevsamp", "varpop", "varsamp"):
        i_s = ctx.add_op(ir.AggOp("sum", vexpr=ctx.value_expr(data[0])))
        i_q = ctx.add_op(ir.AggOp("sumsq", vexpr=ctx.value_expr(data[0])))
        return LoweredAgg(
            label, sem,
            lambda outs, g: (int(outs[0][g]), float(outs[i_s][g]), float(outs[i_q][g])))

    if name in ("skewness", "kurtosis"):
        # cast before powering: int32 column planes overflow at v**4
        v = ir.Cast(ctx.value_expr(data[0]), "DOUBLE")
        i1 = ctx.add_op(ir.AggOp("sum", vexpr=v))
        i2 = ctx.add_op(ir.AggOp("sumsq", vexpr=v))
        i3 = ctx.add_op(ir.AggOp("sum", vexpr=_mul(_mul(v, v), v)))
        i4 = ctx.add_op(ir.AggOp("sum", vexpr=_mul(_mul(v, v), _mul(v, v))))
        return LoweredAgg(
            label, sem,
            lambda outs, g: (int(outs[0][g]), float(outs[i1][g]), float(outs[i2][g]),
                             float(outs[i3][g]), float(outs[i4][g])))

    if name in ("covarpop", "covarsamp", "corr"):
        x = ir.Cast(ctx.value_expr(data[0]), "DOUBLE")
        y = ir.Cast(ctx.value_expr(data[1]), "DOUBLE")
        ix = ctx.add_op(ir.AggOp("sum", vexpr=x))
        iy = ctx.add_op(ir.AggOp("sum", vexpr=y))
        ixy = ctx.add_op(ir.AggOp("sum", vexpr=_mul(x, y)))
        ixx = ctx.add_op(ir.AggOp("sumsq", vexpr=x))
        iyy = ctx.add_op(ir.AggOp("sumsq", vexpr=y))
        return LoweredAgg(
            label, sem,
            lambda outs, g: (int(outs[0][g]), float(outs[ix][g]), float(outs[iy][g]),
                             float(outs[ixy][g]), float(outs[ixx][g]), float(outs[iyy][g])))

    if name in ("booland", "boolor", "boolagg"):
        # booleans are 0/1 ints: AND = min (empty→+inf→True), OR = max (empty→-inf→False)
        kind = "min" if name == "booland" else "max"
        i = ctx.add_op(ir.AggOp(kind, vexpr=ctx.value_expr(data[0])))
        return LoweredAgg(label, sem, lambda outs, g: bool(outs[i][g] > 0.5))

    raise UnsupportedQueryError(f"aggregation {name} not yet lowered to device")


def _int_bounds(ctx, arg) -> dict:
    """Static integer bounds for the 32-bit kernel fast paths (see
    kernels._fits_i32/_segment_sum_exact_i64); {} when unknown or
    non-integer. QUANTIZED to power-of-two envelopes — the bounds are static
    jit args, and per-segment exact min/max would compile a fresh kernel
    per segment."""
    mm = ctx.col_minmax(arg)
    if mm is None:
        return {}
    lo, hi = mm
    if isinstance(lo, (int, np.integer)) and isinstance(hi, (int, np.integer)):
        lo, hi = int(lo), int(hi)
        qhi = (1 << max(hi, 1).bit_length()) - 1 if hi >= 0 else 0
        qlo = 0 if lo >= 0 else -(1 << max(-lo, 1).bit_length())
        return {"vmin": qlo, "vmax": qhi}
    return {}


def _occupancy_op(ctx: AggPlanContext, arg: ExpressionContext, name: str):
    info = ctx.dict_info(arg, sv_only=True)
    if info is None:
        raise UnsupportedQueryError(
            f"{name} needs a dict-encoded SV column: {arg}")
    ids_slot, card, dictionary = info
    i = ctx.add_op(ir.AggOp("distinct_bitmap", ids_slot=ids_slot, card=card))
    return i, dictionary, card


def _occ_row_ids(o: np.ndarray, g) -> np.ndarray:
    """Dict ids present in group g, from either occupancy form:
    - dense: (groups, card) boolean matrix → nonzero of row g
    - sparse: (slots, W) uint32 id bitmap words — little-endian bit j of
      word w encodes dict id w*32+j"""
    if o.dtype == np.uint32:
        return np.nonzero(np.unpackbits(
            np.ascontiguousarray(o[g]).view(np.uint8),
            bitorder="little"))[0]
    return np.nonzero(o[g])[0]


def _occ_ids(outs, i, g, card) -> np.ndarray:
    return _occ_row_ids(outs[i], g)


def _occ_prepare(i: int, card: int, state_fn):
    """Batch extractor for occupancy aggs; both forms decode row-wise
    (sparse bitmap rows are already per-slot).
    state_fn(ids: np.ndarray) builds the per-group state."""

    def prepare(outs):
        o = outs[i]
        return lambda g: state_fn(_occ_row_ids(o, g))

    return prepare


def _value_hist_op(ctx: AggPlanContext, arg: ExpressionContext, name: str):
    info = ctx.dict_info(arg, sv_only=True)
    if info is None:
        raise UnsupportedQueryError(
            f"{name} needs a dict-encoded SV column: {arg}")
    ids_slot, card, dictionary = info
    i = ctx.add_op(ir.AggOp("value_hist", ids_slot=ids_slot, card=card))
    return i, dictionary


def _numeric_dictionary(d) -> bool:
    return np.asarray(d.values).dtype.kind in ("i", "u", "f")


# ---------------------------------------------------------------------------
# Host (numpy) states — used by the fallback engine and the test oracle
# ---------------------------------------------------------------------------


def host_state_full(name: str, cols: list, extra: tuple):
    """Per-group intermediate state from the group's (already filtered) raw
    value arrays — one array per data argument. Must produce states
    mergeable/finalizable by get_semantics — i.e. identical shape to the
    device path's LoweredAgg.extract."""
    name, extra = canonicalize(name, extra)
    values = cols[0] if cols else None
    n = 0 if values is None else len(values)

    if name in ("count", "countmv"):
        return n
    if values is None:
        raise UnsupportedQueryError(f"{name} requires an argument")

    if name in ("sum", "summv"):
        return float(np.sum(values)) if n else 0.0
    if name == "sumprecision":
        # exact decimal sum (reference SumPrecisionAggregationFunction's
        # BigDecimal); column may be stored as strings
        return sum((Decimal(str(v)) for v in values), Decimal(0))
    if name in ("min", "minmv"):
        return float(np.min(values)) if n else math.inf
    if name in ("max", "maxmv"):
        return float(np.max(values)) if n else -math.inf
    if name in ("minmaxrange", "minmaxrangemv"):
        return (float(np.min(values)), float(np.max(values))) if n else (math.inf, -math.inf)
    if name in ("avg", "avgmv"):
        return (float(np.sum(values)), n)
    if name in _EXACT_DISTINCT:
        return frozenset(np.unique(values).tolist())
    if name in ("distinctsum", "distinctavg"):
        return frozenset(float(v) for v in np.unique(values))
    if name in _HLL_FNS:
        log2m = int(extra[0]) if extra else 12
        return HyperLogLog(log2m).add_values(np.unique(values))
    if name in _THETA_FNS:
        return ThetaSketch().add_values(np.unique(values))
    if name in ("distinctcountsmart", "distinctcountsmarthll"):
        return SmartDistinctSet().add_values(np.unique(values))
    if name in _PCT_EXACT or name == "mode":
        if np.asarray(values).dtype.kind not in ("i", "u", "f", "b"):
            raise UnsupportedQueryError(f"{name} requires a numeric column")
        return ValueHist.from_values(values)
    if name in _PCT_DIGEST:
        return TDigest().add_values(np.asarray(values, dtype=np.float64))
    if name == "histogram":
        if len(extra) != 3:
            raise UnsupportedQueryError("histogram(col, lower, upper, numBins)")
        lo, hi, bins = float(extra[0]), float(extra[1]), int(extra[2])
        if hi <= lo or bins <= 0:
            raise UnsupportedQueryError("histogram requires upper > lower and numBins > 0")
        v = np.asarray(values, dtype=np.float64)
        counts, _ = np.histogram(v[(v >= lo) & (v <= hi)], bins=bins, range=(lo, hi))
        return counts.astype(np.float64)
    if name in ("stddevpop", "stddevsamp", "varpop", "varsamp"):
        v = np.asarray(values, dtype=np.float64)
        return (n, float(v.sum()), float((v * v).sum()))
    if name in ("skewness", "kurtosis"):
        v = np.asarray(values, dtype=np.float64)
        return (n, float(v.sum()), float((v**2).sum()), float((v**3).sum()),
                float((v**4).sum()))
    if name in ("covarpop", "covarsamp", "corr"):
        x = np.asarray(cols[0], dtype=np.float64)
        y = np.asarray(cols[1], dtype=np.float64)
        return (n, float(x.sum()), float(y.sum()), float((x * y).sum()),
                float((x * x).sum()), float((y * y).sum()))
    if name == "booland":
        return bool(np.all(values)) if n else True
    if name in ("boolor", "boolagg"):
        return bool(np.any(values)) if n else False
    if name in ("exprmin", "exprmax"):
        # EXPR_MIN(projectionCol, measuringCol)
        proj, measure = cols[0], cols[1]
        if n == 0:
            return None
        idx = int(np.argmin(measure)) if name == "exprmin" else int(np.argmax(measure))
        return (_item(measure[idx]), _item(proj[idx]))
    if name in ("firstwithtime", "lastwithtime"):
        data_col, time_col = cols[0], cols[1]
        if n == 0:
            return None
        idx = int(np.argmin(time_col)) if name == "firstwithtime" else int(np.argmax(time_col))
        return (_item(time_col[idx]), _item(data_col[idx]))
    if name in ("arrayagg", "listagg"):
        return tuple(_item(v) for v in values)
    raise UnsupportedQueryError(f"aggregation {name} not implemented on host")


def host_state(name: str, values: Optional[np.ndarray], extra: tuple = ()):
    """Single-data-argument convenience wrapper (MV flatten path)."""
    return host_state_full(name, [values] if values is not None else [], extra)


def _item(v):
    return v.item() if isinstance(v, np.generic) else v
