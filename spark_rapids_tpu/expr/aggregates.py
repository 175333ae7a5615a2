"""Aggregate functions: update/merge/finalize protocol.

Mirrors the reference's CudfAggregate split into update/merge phases
(reference: org/apache/spark/sql/rapids/aggregate/aggregateFunctions.scala)
so the exec layer can run partial-per-batch aggregation, merge partials on
device, and finalize — for both ungrouped reductions and (sort-based)
grouped aggregation via jax.ops.segment_* primitives.

States are tuples of jnp scalars (ungrouped) or [num_segments] arrays
(grouped). All null semantics follow Spark:
  sum/min/max over zero valid rows -> null; count is never null;
  avg = sum/count, null when count == 0.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..ops.groups import ScatterGroups
from ..ops.kernel_utils import CV
from .expressions import (Cast, Expression, Literal, UnsupportedExpr)

__all__ = ["AggExpr", "Sum", "Count", "CountStar", "Min", "Max", "Avg",
           "First", "Last", "Stddev", "Variance"]

_MINMAX_IDENT = {
    jnp.float32: (jnp.inf, -jnp.inf),
    jnp.float64: (jnp.inf, -jnp.inf),
}


def _ident(np_dtype, for_min: bool):
    if jnp.issubdtype(np_dtype, jnp.floating):
        return jnp.inf if for_min else -jnp.inf
    if np_dtype == jnp.bool_:
        return True if for_min else False
    info = jnp.iinfo(np_dtype)
    return info.max if for_min else info.min


class AggExpr(Expression):
    """An aggregate over a child expression. Not valid in row projections."""

    def __init__(self, child: Optional[Expression]):
        self.child = child
        self.children = [child] if child is not None else []

    def bind(self, schema):
        b = type(self)(self.child.bind(schema) if self.child else None)
        b._resolve_type()
        return b

    def _resolve_type(self):
        raise NotImplementedError

    # --- protocol: ungrouped ------------------------------------------
    # update(cv, mask) -> state (tuple of scalars)
    # merge(s1, s2) -> state
    # finalize(state) -> (scalar_value, scalar_valid)
    def num_state_cols(self) -> int:
        raise NotImplementedError

    def __repr__(self):
        return f"{type(self).__name__.lower()}({self.child})"


class Sum(AggExpr):
    """Sum. Decimal results over precision 18 accumulate EXACTLY as
    per-32-bit-limb int64 partial sums (JNI DecimalUtils sum analog);
    overflow past the result precision yields null (Spark non-ANSI)."""

    state_reducers = ("sum", "or")

    def _resolve_type(self):
        ct = self.child.dtype
        self._d128 = False
        self._in_d128 = False
        if isinstance(ct, dt.DecimalType):
            self.dtype = dt.DecimalType(min(38, ct.precision + 10),
                                        ct.scale)
            if self.dtype.is_decimal128:
                self._d128 = True
                self._in_d128 = ct.is_decimal128
                nlimbs = 4 if self._in_d128 else 2
                self.state_reducers = ("sum",) * nlimbs + ("or",)
        elif ct.is_integral or isinstance(ct, dt.BooleanType):
            self.dtype = dt.INT64
        elif ct.is_floating:
            self.dtype = dt.FLOAT64
        elif isinstance(ct, dt.NullType):
            self.dtype = dt.FLOAT64
        else:
            raise UnsupportedExpr(f"sum({ct})")
        self._acc_dtype = self.dtype.np_dtype

    def _limbs(self, cv: CV, m):
        from ..ops import decimal128 as d128
        if self._in_d128:
            raw = d128.split_d128_limbs(cv.data)
        else:
            raw = d128.split_i64_limbs(cv.data)
        return [jnp.where(m, l, 0) for l in raw]

    def update(self, cv: CV, mask):
        m = mask & cv.validity
        if self._d128:
            limbs = self._limbs(cv, m)
            return tuple(jnp.sum(l) for l in limbs) + (jnp.any(m),)
        x = jnp.where(m, cv.data, 0).astype(self._acc_dtype)
        return (jnp.sum(x), jnp.any(m))

    def merge(self, s1, s2):
        if self._d128:
            return tuple(a + b for a, b in zip(s1[:-1], s2[:-1])) \
                + (s1[-1] | s2[-1],)
        return (s1[0] + s2[0], s1[1] | s2[1])

    def finalize(self, s):
        if self._d128:
            from ..ops import decimal128 as d128
            val, ovf = d128.combine_limb_sums(list(s[:-1]),
                                              self.dtype.precision)
            return val, s[-1] & ~ovf
        return s[0], s[1]

    # --- grouped: per-segment ----
    def g_update(self, cv: CV, mask, groups):
        m = mask & cv.validity
        has = groups.any(m)
        if self._d128:
            limbs = self._limbs(cv, m)
            return tuple(groups.sum(l) for l in limbs) + (has,)
        x = jnp.where(m, cv.data, 0).astype(self._acc_dtype)
        return (groups.sum(x), has)


class Count(AggExpr):
    state_reducers = ("sum",)

    def _resolve_type(self):
        self.dtype = dt.INT64

    def update(self, cv: CV, mask):
        return (jnp.sum((mask & cv.validity).astype(jnp.int64)),)

    def merge(self, s1, s2):
        return (s1[0] + s2[0],)

    def finalize(self, s):
        return s[0], jnp.bool_(True)

    def g_update(self, cv, mask, groups):
        return (groups.sum((mask & cv.validity).astype(jnp.int64)),)


class CountStar(AggExpr):
    state_reducers = ("sum",)

    def __init__(self, child=None):
        super().__init__(None)

    def _resolve_type(self):
        self.dtype = dt.INT64

    def bind(self, schema):
        b = CountStar()
        b._resolve_type()
        return b

    def update(self, cv, mask):
        return (jnp.sum(mask.astype(jnp.int64)),)

    def merge(self, s1, s2):
        return (s1[0] + s2[0],)

    def finalize(self, s):
        return s[0], jnp.bool_(True)

    def g_update(self, cv, mask, groups):
        return (groups.sum(mask.astype(jnp.int64)),)

    def __repr__(self):
        return "count(*)"


def _d128_sortable(data2):
    """[cap,2] -> (hi, lo') where lexicographic (hi, lo') min/max equals
    the signed 128-bit min/max: hi signed, lo bias-flipped to signed-
    comparable unsigned order."""
    hi = data2[:, 1]
    lo = data2[:, 0] ^ jnp.int64(-(1 << 63))
    return hi, lo


def _d128_unsortable(hi, lo):
    return jnp.stack([lo ^ jnp.int64(-(1 << 63)), hi], axis=-1)


class _MinMax(AggExpr):
    for_min = True

    @property
    def state_reducers(self):
        if getattr(self, "_d128_in", False):
            return ("custom",)
        return ("min" if self.for_min else "max", "or")

    def _resolve_type(self):
        ct = self.child.dtype
        if ct.is_variable_width or ct.is_nested:
            raise UnsupportedExpr(f"min/max({ct}) round-1")
        self._d128_in = (isinstance(ct, dt.DecimalType)
                         and ct.is_decimal128)
        self.dtype = ct

    def _masked(self, cv, m):
        """Mask invalid rows to the identity; for float min, NaN (greatest
        per Spark ordering) must lose to any real value, so map it to +inf
        (documented deviation: an all-NaN min yields +inf, not NaN)."""
        ident = _ident(cv.data.dtype, self.for_min)
        x = jnp.where(m, cv.data, ident)
        if self.for_min and jnp.issubdtype(x.dtype, jnp.floating):
            x = jnp.where(jnp.isnan(x), jnp.inf, x)
        return x

    # -- decimal128: lexicographic (hi, lo') reduction -------------------
    def _d128_masked(self, cv, m):
        hi, lo = _d128_sortable(cv.data)
        ident_hi = _ident(jnp.dtype(jnp.int64), self.for_min)
        hi = jnp.where(m, hi, ident_hi)
        lo = jnp.where(m, lo, ident_hi)
        return hi, lo

    @staticmethod
    def _lex_pick(for_min, h1, l1, h2, l2):
        take1 = (h1 < h2) | ((h1 == h2) & (l1 <= l2))
        if not for_min:
            take1 = (h1 > h2) | ((h1 == h2) & (l1 >= l2))
        return (jnp.where(take1, h1, h2), jnp.where(take1, l1, l2))

    def num_state_cols(self):
        return 3 if getattr(self, "_d128_in", False) else 2

    def update(self, cv: CV, mask):
        m = mask & cv.validity
        if getattr(self, "_d128_in", False):
            hi, lo = self._d128_masked(cv, m)
            # reduce hi first, then lo among rows holding the winning hi
            red_hi = jnp.min(hi) if self.for_min else jnp.max(hi)
            cand = jnp.where(hi == red_hi, lo,
                             _ident(jnp.dtype(jnp.int64), self.for_min))
            red_lo = jnp.min(cand) if self.for_min else jnp.max(cand)
            return (red_hi, red_lo, jnp.any(m))
        x = self._masked(cv, m)
        red = jnp.min(x) if self.for_min else jnp.max(x)
        return (red, jnp.any(m))

    def merge(self, s1, s2):
        if getattr(self, "_d128_in", False):
            h, l = self._lex_pick(self.for_min, s1[0], s1[1], s2[0], s2[1])
            return (h, l, s1[2] | s2[2])
        v = jnp.minimum(s1[0], s2[0]) if self.for_min else jnp.maximum(
            s1[0], s2[0])
        # all-invalid partials carry the identity, so plain min/max is safe
        return (v, s1[1] | s2[1])

    def finalize(self, s):
        if getattr(self, "_d128_in", False):
            return _d128_unsortable(s[0], s[1]), s[2]
        return s[0], s[1]

    def g_update(self, cv, mask, groups):
        m = mask & cv.validity
        if getattr(self, "_d128_in", False):
            # custom: the low limb's candidates read the winning high
            # limb back by id, so this one scatters in either layout
            seg_ids, num_segments = groups.seg_ids, groups.num_segments
            hi, lo = self._d128_masked(cv, m)
            seg = (jax.ops.segment_min if self.for_min
                   else jax.ops.segment_max)
            red_hi = seg(hi, seg_ids, num_segments)
            ident = _ident(jnp.dtype(jnp.int64), self.for_min)
            cand = jnp.where(hi == red_hi[seg_ids], lo, ident)
            red_lo = seg(cand, seg_ids, num_segments)
            has = jax.ops.segment_max(m.astype(jnp.int32), seg_ids,
                                      num_segments) > 0
            return (red_hi, red_lo, has)
        x = self._masked(cv, m)
        red = groups.min(x) if self.for_min else groups.max(x)
        return (red, groups.any(m))

    def g_merge_custom(self, cols_sorted, live, groups):
        seg_ids, num_segments = groups.seg_ids, groups.num_segments
        hi, lo, has = cols_sorted
        eligible = live & has.astype(jnp.bool_)
        ident = _ident(jnp.dtype(jnp.int64), self.for_min)
        hi_m = jnp.where(eligible, hi, ident)
        lo_m = jnp.where(eligible, lo, ident)
        seg = (jax.ops.segment_min if self.for_min
               else jax.ops.segment_max)
        red_hi = seg(hi_m, seg_ids, num_segments)
        cand = jnp.where((hi_m == red_hi[seg_ids]) & eligible, lo_m,
                         ident)
        red_lo = seg(cand, seg_ids, num_segments)
        has_out = jax.ops.segment_max(eligible.astype(jnp.int32), seg_ids,
                                      num_segments) > 0
        return (red_hi, red_lo, has_out)


class Min(_MinMax):
    for_min = True


class Max(_MinMax):
    for_min = False


class Avg(AggExpr):
    state_reducers = ("sum", "sum")

    def _resolve_type(self):
        ct = self.child.dtype
        if isinstance(ct, dt.DecimalType):
            if ct.is_decimal128:
                raise UnsupportedExpr(
                    "avg over decimal precision > 18 (sum/count it "
                    "explicitly, or cast)")
            s = min(ct.scale + 4, 18)
            self.dtype = dt.DecimalType(18, s)
            self._sum_scale = ct.scale
        elif ct.is_integral or isinstance(ct, dt.BooleanType):
            # Spark computes avg(long) from the wrapping int64 sum
            self.dtype = dt.FLOAT64
            self._sum_scale = None
            self._int_acc = True
        elif ct.is_numeric or isinstance(ct, dt.NullType):
            self.dtype = dt.FLOAT64
            self._sum_scale = None
            self._int_acc = False
        else:
            raise UnsupportedExpr(f"avg({ct})")

    def _acc(self, cv, m):
        if self._sum_scale is not None or getattr(self, "_int_acc", False):
            return jnp.where(m, cv.data, 0).astype(jnp.int64)
        return jnp.where(m, cv.data, 0).astype(jnp.float64)

    def update(self, cv: CV, mask):
        m = mask & cv.validity
        x = self._acc(cv, m)
        return (jnp.sum(x), jnp.sum(m.astype(jnp.int64)))

    def merge(self, s1, s2):
        return (s1[0] + s2[0], s1[1] + s2[1])

    def finalize(self, s):
        total, cnt = s
        valid = cnt > 0
        safe = jnp.where(valid, cnt, 1)
        if self._sum_scale is not None:
            shift = self.dtype.scale - self._sum_scale
            num = total * (10 ** shift)
            half = safe // 2
            adj = jnp.where(num >= 0, num + half, num - half)
            q = adj // safe
            r = adj - q * safe
            q = jnp.where((r != 0) & (adj < 0), q + 1, q)
            return q, valid
        return total.astype(jnp.float64) / safe, valid

    def g_update(self, cv, mask, groups):
        m = mask & cv.validity
        return (groups.sum(self._acc(cv, m)),
                groups.sum(m.astype(jnp.int64)))


def _seg_extreme_pos(eligible, seg_ids, num_segments, take_first: bool):
    """Per-segment position of the first/last eligible row ->
    (safe_index, found). Shared by _FirstLast update/merge paths."""
    n = eligible.shape[0]
    idxs = jnp.arange(n)
    sentinel = n if take_first else -1
    cand = jnp.where(eligible, idxs, sentinel)
    seg = jax.ops.segment_min if take_first else jax.ops.segment_max
    pos = seg(cand, seg_ids, num_segments)
    found = (pos < n) if take_first else (pos >= 0)
    return jnp.clip(pos, 0, n - 1), found


class _FirstLast(AggExpr):
    """State (value, valid, has): `has` marks whether an eligible row was
    seen. Grouped merge picks the first/last eligible partial in concat
    order (the stable key sort preserves it) via g_merge_custom."""

    take_first = True
    state_reducers = ("custom",)

    def __init__(self, child, ignore_nulls: bool = False):
        super().__init__(child)
        self.ignore_nulls = ignore_nulls

    def bind(self, schema):
        b = type(self)(self.child.bind(schema), self.ignore_nulls)
        b._resolve_type()
        return b

    def _resolve_type(self):
        ct = self.child.dtype
        if ct.is_nested:
            raise UnsupportedExpr("first/last on nested input")
        if ct.is_variable_width:
            # strings/binary can't ride the fixed-width state wire:
            # route through the sort-collect path (raw rows exchanged on
            # the grouping keys), where a per-segment positional select
            # serves first/last in input order
            self.is_collect = True
        self.dtype = ct

    def update(self, cv: CV, mask):
        m = mask & (cv.validity if self.ignore_nulls else
                    jnp.ones_like(cv.validity))
        n = m.shape[0]
        idxs = jnp.arange(n)
        sentinel = n if self.take_first else -1
        cand = jnp.where(m, idxs, sentinel)
        pos = jnp.min(cand) if self.take_first else jnp.max(cand)
        has = (pos < n) if self.take_first else (pos >= 0)
        safe = jnp.clip(pos, 0, n - 1)
        return (cv.data[safe], cv.validity[safe] & has, has)

    def merge(self, s1, s2):
        a, b = (s1, s2) if self.take_first else (s2, s1)
        take_a = a[2]
        return (jnp.where(take_a, a[0], b[0]),
                jnp.where(take_a, a[1], b[1]), a[2] | b[2])

    def finalize(self, s):
        return s[0], s[1]

    def num_state_cols(self):
        return 3

    def g_update(self, cv, mask, groups):
        seg_ids, num_segments = groups.seg_ids, groups.num_segments
        m = mask & (cv.validity if self.ignore_nulls else
                    jnp.ones_like(cv.validity))
        safe, has = _seg_extreme_pos(m, seg_ids, num_segments,
                                     self.take_first)
        return (cv.data[safe], cv.validity[safe] & has, has)

    def g_merge_custom(self, cols_sorted, live, groups):
        seg_ids, num_segments = groups.seg_ids, groups.num_segments
        val, valid, has = cols_sorted
        eligible = live & has.astype(jnp.bool_)
        safe, found = _seg_extreme_pos(eligible, seg_ids, num_segments,
                                       self.take_first)
        return (val[safe], valid[safe].astype(jnp.bool_) & found, found)


class First(_FirstLast):
    take_first = True


class Last(_FirstLast):
    take_first = False


class Variance(AggExpr):
    """var_samp (Spark variance) with Welford/Chan merging — the
    E[x^2]-E[x]^2 form catastrophically cancels for large-magnitude
    inputs. State: (n, mean, M2); batch update computes the per-segment
    mean then M2 = sum((x-mean)^2); merges use Chan's formula via a
    custom grouped merge (reference: aggregateFunctions.scala M2-based
    variance)."""

    state_reducers = ("custom",)  # uses g_merge_custom
    ddof = 1

    def _resolve_type(self):
        ct = self.child.dtype
        if not (ct.is_numeric or isinstance(ct, dt.NullType)):
            raise UnsupportedExpr(f"variance({ct})")
        if isinstance(ct, dt.DecimalType) and ct.is_decimal128:
            raise UnsupportedExpr(
                "variance over decimal precision > 18 (cast first)")
        self.dtype = dt.FLOAT64
        self._scale = (10.0 ** -ct.scale
                       if isinstance(ct, dt.DecimalType) else 1.0)

    def num_state_cols(self):
        return 3

    def _xs(self, cv, m):
        return jnp.where(m, cv.data, 0).astype(jnp.float64) * self._scale

    # ---- ungrouped ----------------------------------------------------
    def update(self, cv: CV, mask):
        m = mask & cv.validity
        x = self._xs(cv, m)
        n = jnp.sum(m.astype(jnp.int64))
        nf = jnp.maximum(n, 1).astype(jnp.float64)
        mean = jnp.sum(x) / nf
        d = jnp.where(m, x - mean, 0.0)
        m2 = jnp.sum(d * d)
        return (n, mean, m2)

    def merge(self, s1, s2):
        n1, m1, q1 = s1
        n2, m2_, q2 = s2
        n = n1 + n2
        nf = jnp.maximum(n, 1).astype(jnp.float64)
        delta = m2_ - m1
        mean = m1 + delta * (n2.astype(jnp.float64) / nf)
        q = (q1 + q2 + delta * delta
             * (n1.astype(jnp.float64) * n2.astype(jnp.float64) / nf))
        return (n, mean, q)

    def finalize(self, s):
        n, _, m2 = s
        valid = n > self.ddof
        denom = jnp.where(valid, (n - self.ddof).astype(jnp.float64), 1.0)
        return self._final_value(jnp.maximum(m2, 0.0) / denom), valid

    def _final_value(self, var):
        return var

    # ---- grouped ------------------------------------------------------
    def g_update(self, cv, mask, groups):
        seg_ids, num_segments = groups.seg_ids, groups.num_segments
        m = mask & cv.validity
        x = self._xs(cv, m)
        n = jax.ops.segment_sum(m.astype(jnp.int64), seg_ids, num_segments)
        nf = jnp.maximum(n, 1).astype(jnp.float64)
        mean = jax.ops.segment_sum(x, seg_ids, num_segments) / nf
        d = jnp.where(m, x - mean[seg_ids], 0.0)
        m2 = jax.ops.segment_sum(d * d, seg_ids, num_segments)
        return (n, mean, m2)

    def g_merge_custom(self, cols_sorted, live, groups):
        seg_ids, num_segments = groups.seg_ids, groups.num_segments
        """Chan's parallel combine across partial states of one segment:
        Mean = sum(n_i mean_i)/N; M2 = sum(M2_i) + sum(n_i (mean_i-Mean)^2).
        Differences of means stay small, so no cancellation."""
        n_i, mean_i, m2_i = cols_sorted
        n_i = jnp.where(live, n_i, 0)
        mean_i = jnp.where(live, mean_i, 0.0)
        m2_i = jnp.where(live, m2_i, 0.0)
        N = jax.ops.segment_sum(n_i, seg_ids, num_segments)
        Nf = jnp.maximum(N, 1).astype(jnp.float64)
        Mean = jax.ops.segment_sum(
            n_i.astype(jnp.float64) * mean_i, seg_ids, num_segments) / Nf
        dev = mean_i - Mean[seg_ids]
        M2 = (jax.ops.segment_sum(m2_i, seg_ids, num_segments)
              + jax.ops.segment_sum(
                  n_i.astype(jnp.float64) * dev * dev, seg_ids,
                  num_segments))
        return (N, Mean, M2)


class Stddev(Variance):
    """stddev_samp (Spark stddev)."""

    def _final_value(self, var):
        return jnp.sqrt(var)


class _Collect(AggExpr):
    """collect_list / collect_set (reference: aggregateFunctions.scala
    GpuCollectList/GpuCollectSet over cudf collect aggregations).

    Variable-width result: runs on CollectAggExec's sort path (one stable
    sort by keys makes each group's values contiguous — the sorted value
    column IS the concatenated list child), not the flat-state machinery.
    `state_reducers = None` keeps HashAggregateExec from accepting it."""

    state_reducers = None
    is_collect = True
    is_set = False

    def _resolve_type(self):
        from ..columnar import dtypes as _dt
        if self.child.dtype.is_nested:
            raise UnsupportedExpr(
                f"{type(self).__name__.lower()} over nested input")
        self.dtype = _dt.ArrayType(self.child.dtype, contains_null=False)


class CollectList(_Collect):
    def __repr__(self):
        return f"collect_list({self.child})"


class CollectSet(_Collect):
    is_set = True

    def __repr__(self):
        return f"collect_set({self.child})"


class CountDistinct(_Collect):
    """count(DISTINCT x) via the sort path: per-group first-occurrence
    flags from a segmented value sort (reference: distinct-agg rewrite +
    cudf distinct count)."""

    is_set = True       # needs per-agg value ordering for dedup
    is_collect = True

    def _resolve_type(self):
        from ..columnar import dtypes as _dt
        if self.child.dtype.is_nested:
            raise UnsupportedExpr("count distinct over nested input")
        self.dtype = _dt.INT64

    def __repr__(self):
        return f"count(DISTINCT {self.child})"


class _HllHash(Expression):
    """Internal: murmur3(child) with the CHILD's validity (nulls skip —
    unlike the user-facing Murmur3Hash whose null folds to the seed).
    Makes the HLL agg input fixed-width int32, so strings/decimals ride
    the grouped agg paths that strip var-width agg inputs."""

    def __init__(self, child):
        self.child = child
        self.children = [child]
        self.dtype = dt.INT32

    def bind(self, schema):
        return _HllHash(self.child.bind(schema))

    def emit(self, ctx):
        from ..ops.hash import murmur3_cv
        cv = self.child.emit(ctx)
        h = murmur3_cv(cv, self.child.dtype, jnp.int32(42))
        return CV(h, cv.validity)

    def __repr__(self):
        return f"hll_hash({self.child})"


def _clz32(x):
    """Vectorized count-leading-zeros over uint32 (5-step binary
    search; no clz primitive in XLA HLO)."""
    x = x.astype(jnp.uint32)
    zero = x == 0
    c = jnp.zeros(x.shape, jnp.int32)
    for sh in (16, 8, 4, 2, 1):
        cond = x < (jnp.uint32(1) << (32 - sh))
        c = c + jnp.where(cond, sh, 0)
        x = jnp.where(cond, x << sh, x)
    return jnp.where(zero, 32, c)


class ApproxCountDistinct(AggExpr):
    """approx_count_distinct as HyperLogLog++ with O(2^p) register state
    — bounded across the exchange regardless of cardinality (reference:
    GpuHyperLogLogPlusPlus in org/apache/spark/sql/rapids/aggregate/,
    cuDF JNI HLLPP kernels).

    TPU-first layout: the 2^p byte registers of every group pack 8-per-
    int64 into W = 2^p / 8 ordinary state COLUMNS, so partial states ride
    the existing partial/final wire schema, spill framework, and mesh
    exchange like any other aggregate. update computes (register-index,
    rho) per row from the engine's 32-bit murmur3 (via the bound _HllHash
    child, so any input type arrives as int32) and runs ONE segment_max
    over combined (segment * m + register) ids — output memory is
    O(num_segments * 2^p), which on the FIRST per-batch update means
    O(batch_cap * 2^p) int32 (e.g. 4096-row batches at p=9: 8 MB; size
    batches accordingly for small rsd) and collapses to O(groups * 2^p)
    after the first merge. Merge is a per-byte max of packed words
    (custom segmented reducer). Estimation uses the HLL++ alpha with
    linear counting below 2.5m and the 32-bit large-range correction;
    the empirical bias table is omitted (documented in
    docs/compatibility.md — worst case a few percent in the 2.5m..5m
    band, still within typical rsd use).

    rsd -> p via rsd = 1.04/sqrt(2^p), clamped to [4, 12].
    """

    state_reducers = ("custom",)

    def __init__(self, child, rsd: float = 0.05):
        super().__init__(child)
        self.rsd = rsd
        import math
        p = math.ceil(2 * math.log2(1.04 / rsd))
        self.p = max(4, min(12, p))
        self.m = 1 << self.p
        self.W = self.m // 8

    def bind(self, schema):
        bc = self.child.bind(schema)
        if bc.dtype.is_nested:
            raise UnsupportedExpr("approx_count_distinct over nested")
        b = type(self)(_HllHash(bc), self.rsd)
        b._resolve_type()
        return b

    def _resolve_type(self):
        self.dtype = dt.INT64

    def num_state_cols(self):
        return self.W

    # -- hashing --------------------------------------------------------
    def _idx_rho(self, cv: CV, mask):
        # child is _HllHash: cv.data IS the 32-bit hash, validity is the
        # original child's (nulls excluded)
        hu = cv.data.astype(jnp.uint32)
        valid = mask & cv.validity
        idx = (hu >> (32 - self.p)).astype(jnp.int32)
        w = hu << self.p
        rho = _clz32(w) + 1          # 1..(32-p)+1; w==0 -> 33-p cap
        rho = jnp.minimum(rho, 32 - self.p + 1)
        rho = jnp.where(valid, rho, 0).astype(jnp.int32)
        idx = jnp.where(valid, idx, 0)
        return idx, rho

    def _pack(self, regs2d):
        """(nseg, m) int32 registers -> tuple of W packed int64 words."""
        n = regs2d.shape[0]
        r = regs2d.reshape(n, self.W, 8).astype(jnp.int64)
        shifts = (jnp.arange(8, dtype=jnp.int64) * 8)[None, None, :]
        words = jnp.sum(r << shifts, axis=2)      # (nseg, W)
        return tuple(words[:, i] for i in range(self.W))

    @staticmethod
    def _unpack(words):
        """list of W (n,) int64 -> (n, m) int32 registers."""
        return ApproxCountDistinct._unpack_stacked(
            jnp.stack(words, axis=1))

    @staticmethod
    def _unpack_stacked(stacked):
        """(n, W) packed int64 -> (n, m) int32 registers."""
        shifts = (jnp.arange(8, dtype=jnp.int64) * 8)[None, None, :]
        bytes_ = (stacked[:, :, None] >> shifts) & jnp.int64(0xFF)
        n = stacked.shape[0]
        return bytes_.reshape(n, -1).astype(jnp.int32)

    # -- grouped --------------------------------------------------------
    def g_update(self, cv: CV, mask, groups):
        seg_ids, num_segments = groups.seg_ids, groups.num_segments
        idx, rho = self._idx_rho(cv, mask)
        # combined (segment, register) key -> one segment_max over
        # num_segments * m slots. Memory is O(cap + num_segments * m);
        # the with_retry split bounds cap, and num_segments collapses to
        # the actual group capacity after the first merge.
        comb = seg_ids.astype(jnp.int64) * self.m + idx.astype(jnp.int64)
        regs = jax.ops.segment_max(rho, comb, num_segments * self.m)
        # empty (segment, register) slots come back as int32-min (the
        # segment_max identity) — clamp to 0 before byte-packing
        regs = jnp.maximum(regs, 0)
        words = self._pack(regs.reshape(num_segments, self.m))
        return tuple(words)

    def g_merge_custom(self, cols_sorted, live, groups):
        seg_ids, num_segments = groups.seg_ids, groups.num_segments
        regs = self._unpack(list(cols_sorted))    # (cap, m)
        regs = jnp.where(live[:, None], regs, 0)
        merged = jax.ops.segment_max(regs, seg_ids, num_segments)
        return self._pack(jnp.maximum(merged, 0))  # empty seg -> int-min

    # -- ungrouped ------------------------------------------------------
    # State is ONE (W,) vector (not W scalars: the runtime dedups
    # aliased same-buffer args, and W slices of one packed array broke
    # the compiled arg count).
    def update(self, cv: CV, mask):
        zeros = jnp.zeros(mask.shape[0], jnp.int32)
        words = self.g_update(cv, mask, ScatterGroups(zeros, 1))
        return (jnp.stack([w[0] for w in words]),)

    def merge(self, s1, s2):
        r1 = self._unpack_stacked(s1[0][None, :])
        r2 = self._unpack_stacked(s2[0][None, :])
        packed = self._pack(jnp.maximum(r1, r2))
        return (jnp.stack([w[0] for w in packed]),)

    def finalize(self, s):
        arrs = list(s)
        # ungrouped state is ONE (W,) vector; grouped is W >= 2 columns
        ungrouped = len(arrs) == 1 and arrs[0].ndim == 1
        if ungrouped:
            regs = self._unpack_stacked(arrs[0][None, :])
        else:
            regs = self._unpack(arrs)             # (n, m)
        m = float(self.m)
        alpha = 0.7213 / (1.0 + 1.079 / m)
        inv = jnp.sum(jnp.exp2(-regs.astype(jnp.float64)), axis=1)
        e_raw = alpha * m * m / inv
        zeros = jnp.sum((regs == 0).astype(jnp.float64), axis=1)
        lin = m * jnp.log(m / jnp.maximum(zeros, 1.0))
        est = jnp.where((e_raw <= 2.5 * m) & (zeros > 0), lin, e_raw)
        two32 = 4294967296.0
        est = jnp.where(
            est > two32 / 30.0,
            -two32 * jnp.log1p(-jnp.minimum(est, two32 * 0.999) / two32),
            est)
        out = jnp.round(est).astype(jnp.int64)
        if ungrouped:
            return out[0], jnp.bool_(True)
        return out, jnp.ones(out.shape[0], jnp.bool_)

    def __repr__(self):
        return f"approx_count_distinct({self.child}, rsd={self.rsd})"


class BloomFilterAggregate(AggExpr):
    """bloom_filter_agg: builds an m-bit Bloom filter over the input
    (reference: GpuBloomFilterAggregate.scala + JNI BloomFilter kernels
    — there the filter feeds InSubqueryExec runtime filtering; here the
    companion expression is BloomFilterMightContain).

    TPU-first layout: the filter lives as ONE device bool vector of
    num_bits (update is a scatter of k=hash positions per row — no
    byte-packing in the hot loop); finalize packs little-endian bytes
    (BinaryType), 'k|num_bits' prefixed, which BloomFilterMightContain
    unpacks back to a device vector. Hash scheme: two 32-bit murmur3
    passes (seed 0 / seed 0x97B3AA8C) combine as h1 + i*h2 like Spark's
    split-64 scheme. Ungrouped only, matching Spark (the agg returns
    ONE filter for the build side)."""

    state_reducers = None            # grouped path unsupported

    def __init__(self, child, estimated_items: int = 1_000_000,
                 num_bits: int = None):
        super().__init__(child)
        if num_bits is None:
            # Spark default sizing: ~8 bits/item
            num_bits = max(64, int(estimated_items) * 8)
        # cap below 2^31: positions are int32 on device, and Spark caps
        # runtime.bloomFilter.maxNumBits similarly
        num_bits = min(int(num_bits), 1 << 30)
        self.num_bits = 1 << max(6, int(num_bits - 1).bit_length())
        self.k = 5

    def bind(self, schema):
        b = type(self)(self.child.bind(schema), num_bits=self.num_bits)
        b._resolve_type()
        return b

    def _resolve_type(self):
        ct = self.child.dtype
        if ct.is_nested:
            raise UnsupportedExpr("bloom_filter_agg over nested input")
        self.dtype = dt.BINARY

    def _positions(self, cv: CV, mask):
        from ..ops.hash import bloom_positions
        masked = CV(cv.data, mask & cv.validity, cv.offsets,
                    cv.children)
        return bloom_positions(masked, self.child.dtype, self.k,
                               self.num_bits)

    def update(self, cv: CV, mask):
        # dead rows route to a SACRIFICIAL slot (num_bits) rather than
        # clipping onto bit 0 — a duplicate-index scatter .set() picks
        # arbitrarily, so a dead row's False could clobber a real True
        bits = jnp.zeros(self.num_bits + 1, jnp.bool_)
        for p in self._positions(cv, mask):
            tgt = jnp.where(p >= 0, p, self.num_bits)
            bits = bits.at[tgt].set(True)
        return (bits[:self.num_bits],)

    def merge(self, s1, s2):
        return (s1[0] | s2[0],)

    def finalize(self, s):
        # pack bool bits -> little-endian uint8 bytes on device and emit
        # as ONE BinaryType value: 'BF1|k|num_bits|' + packed
        import numpy as np
        bits = s[0].reshape(-1, 8).astype(jnp.uint8)
        shifts = jnp.arange(8, dtype=jnp.uint8)
        packed = jnp.sum(bits << shifts, axis=1).astype(jnp.uint8)
        head = np.frombuffer(
            f"BF1|{self.k}|{self.num_bits}|".encode(), np.uint8)
        data = jnp.concatenate([jnp.asarray(head), packed])
        off = jnp.array([0, data.shape[0]], jnp.int32)
        v = CV(data, jnp.ones(1, jnp.bool_), off)
        return v, jnp.bool_(True)

    def __repr__(self):
        return f"bloom_filter_agg({self.child}, bits={self.num_bits})"


def parse_bloom_filter(blob: bytes):
    """'BF1|k|num_bits|'-prefixed packed filter -> (k, num_bits,
    numpy bool bit vector)."""
    import numpy as np
    if not blob.startswith(b"BF1|"):
        raise ValueError("not a bloom filter payload")
    _, k, m, rest = blob.split(b"|", 3)
    bits = np.unpackbits(np.frombuffer(rest, np.uint8),
                         bitorder="little")
    return int(k), int(m), bits.astype(bool)


class Percentile(_Collect):
    """percentile / percentile_approx / median over the segmented value
    sort: values of each group are contiguous and ordered after the
    secondary sort, so rank selection is one gather
    (reference: GpuApproximatePercentile's t-digest — here the sort path
    yields EXACT percentiles, an accuracy superset; the accuracy argument
    is accepted and ignored)."""

    is_set = True        # percentile needs per-agg value ordering
    is_collect = True
    interpolate = True   # percentile(): linear interpolation

    def __init__(self, child, percentages, accuracy: int = 10000):
        super().__init__(child)
        self.scalar_out = not isinstance(percentages, (list, tuple))
        self.percentages = ([float(percentages)] if self.scalar_out
                            else [float(p) for p in percentages])
        for p in self.percentages:
            if not (0.0 <= p <= 1.0):
                raise ValueError(f"percentage out of [0,1]: {p}")
        self.accuracy = accuracy

    def bind(self, schema):
        b = type(self)(self.child.bind(schema), 
                       (self.percentages[0] if self.scalar_out
                        else list(self.percentages)), self.accuracy)
        b._resolve_type()
        return b

    def _resolve_type(self):
        from ..columnar import dtypes as _dt
        ct = self.child.dtype
        if not ct.is_numeric or (isinstance(ct, _dt.DecimalType)):
            raise UnsupportedExpr(f"percentile over {ct}")
        elem = _dt.FLOAT64 if self.interpolate else ct
        self.dtype = elem if self.scalar_out else _dt.ArrayType(elem)

    def __repr__(self):
        return f"percentile({self.child}, {self.percentages})"


class ApproxPercentile(Percentile):
    """percentile_approx as a t-digest sketch with O(C) centroid state —
    bounded across the exchange regardless of group size (reference:
    GpuApproximatePercentile.scala + cuDF tdigest kernels; Spark CPU's
    QuantileSummaries).

    TPU-first layout: C rank-bucketed centroids per group stored as
    2C+2 ordinary float64 state COLUMNS (means..., weights..., min,
    max), so partial digests ride the existing partial/final wire
    schema, spill framework, and mesh exchange like any other
    aggregate. update sorts the batch by (segment, validity, value) —
    three stable argsorts, no data-dependent control flow — and bins
    within-group ranks through the t-digest k1 scale function
    k(q) = (C/pi)(asin(2q-1) + pi/2), then ONE segment_sum over
    combined (segment * C + bin) ids. merge flattens buffered digests
    to rows*C candidate centroids, re-sorts by (segment, mean), and
    re-bins cumulative-weight midpoints through the same scale
    function. finalize interpolates piecewise-linearly between centroid
    midrank/mean points with min/max sharpening at the tails.

    Like the reference (which returns cuDF t-digest doubles), results
    are float64 approximations, NOT exact input elements as Spark CPU
    returns (docs/compatibility.md); worst-case rank error per bucket
    is ~pi/(2C) at the median and tighter toward the tails.
    accuracy maps to C = clamp(accuracy // 50, 16, 128)."""

    is_set = False
    is_collect = False
    state_reducers = ("custom",)
    sort_free_update = False    # g_update sorts internally: keep it off
                                # the no-sort hash-bucket first pass

    def __init__(self, child, percentages, accuracy: int = 10000):
        super().__init__(child, percentages, accuracy)
        if int(accuracy) <= 0:
            raise ValueError(
                f"accuracy must be greater than 0 (got {accuracy})")
        self.C = max(16, min(128, int(accuracy) // 50))

    def num_state_cols(self):
        return 2 * self.C + 2

    def _kbin(self, q):
        """k1 scale function -> centroid bin in [0, C-1]."""
        C = self.C
        t = ((jnp.arcsin(jnp.clip(2.0 * q - 1.0, -1.0, 1.0))
              + (jnp.pi / 2)) * (C / jnp.pi))
        return jnp.clip(t.astype(jnp.int32), 0, C - 1)

    @staticmethod
    def _sort3(minor, mid, major):
        """Stable argsort by (major, mid, minor) via composed stable
        single-key sorts (least-significant first)."""
        p = jnp.argsort(minor, stable=True)
        p = p[jnp.argsort(mid[p], stable=True)]
        return p[jnp.argsort(major[p], stable=True)]

    # -- grouped --------------------------------------------------------
    def g_update(self, cv: CV, mask, groups):
        seg_ids, num_segments = groups.seg_ids, groups.num_segments
        C = self.C
        cap = mask.shape[0]
        valid = mask & cv.validity
        x = cv.data.astype(jnp.float64)
        # sort rows by (segment, invalid-last, value); NaN values sort
        # after +inf (jnp.argsort NaN-last), i.e. NaN > everything —
        # Java Double.compare ordering, like Spark CPU
        perm = self._sort3(x, jnp.logical_not(valid).astype(jnp.uint8),
                           seg_ids)
        sseg = seg_ids[perm]
        sval = x[perm]
        svalid = valid[perm]
        pos = jnp.arange(cap)
        segstart = jax.ops.segment_min(pos, sseg, num_segments)[sseg]
        rank = (pos - segstart).astype(jnp.float64)
        ng = jax.ops.segment_sum(valid.astype(jnp.float64), seg_ids,
                                 num_segments)
        q = (rank + 0.5) / jnp.maximum(ng[sseg], 1.0)
        b = self._kbin(q)
        comb = sseg.astype(jnp.int64) * C + b.astype(jnp.int64)
        w = svalid.astype(jnp.float64)
        wsum = jax.ops.segment_sum(w, comb, num_segments * C)
        xsum = jax.ops.segment_sum(jnp.where(svalid, sval, 0.0), comb,
                                   num_segments * C)
        means = jnp.where(wsum > 0, xsum / jnp.maximum(wsum, 1.0), 0.0)
        # NaN is the GREATEST value (Java Double ordering): exclude it
        # from vmin — the state identity stays +inf (all-NaN groups
        # resolve to vmax at finalize) — but let it propagate via vmax
        fin = valid & jnp.logical_not(jnp.isnan(x))
        vmax = jax.ops.segment_max(jnp.where(valid, x, -jnp.inf),
                                   seg_ids, num_segments)
        vmin = jax.ops.segment_min(jnp.where(fin, x, jnp.inf),
                                   seg_ids, num_segments)
        mm = means.reshape(num_segments, C)
        wm = wsum.reshape(num_segments, C)
        return (tuple(mm[:, i] for i in range(C))
                + tuple(wm[:, i] for i in range(C)) + (vmin, vmax))

    def g_merge_custom(self, cols_sorted, live, groups):
        seg_ids, num_segments = groups.seg_ids, groups.num_segments
        C = self.C
        means = jnp.stack(cols_sorted[:C], axis=1)          # (cap, C)
        ws = jnp.stack(cols_sorted[C:2 * C], axis=1)
        vmin = cols_sorted[2 * C]
        vmax = cols_sorted[2 * C + 1]
        ws = jnp.where(live[:, None], ws, 0.0)
        fm = means.reshape(-1)
        fw = ws.reshape(-1)
        fseg = jnp.repeat(seg_ids, C)
        nm, nw = self._recompress(fm, fw, fseg, num_segments)
        nvmin = jax.ops.segment_min(
            jnp.where(live, vmin, jnp.inf), seg_ids, num_segments)
        nvmax = jax.ops.segment_max(
            jnp.where(live, vmax, -jnp.inf), seg_ids, num_segments)
        return (tuple(nm[:, i] for i in range(C))
                + tuple(nw[:, i] for i in range(C)) + (nvmin, nvmax))

    def _recompress(self, fm, fw, fseg, num_segments):
        """Merge flat candidate centroids (mean fm, weight fw, segment
        fseg) into (num_segments, C) digests: sort by (segment,
        empty-last, mean), re-bin cumulative-weight midpoints through
        the scale function, one combined segment_sum."""
        C = self.C
        n = fm.shape[0]
        key = jnp.where(fw > 0, fm, jnp.inf)     # empty slots last
        p = self._sort3(key, (fw <= 0).astype(jnp.uint8), fseg)
        sseg = fseg[p]
        sw = fw[p]
        sm = jnp.where(fw[p] > 0, fm[p], 0.0)    # no 0*inf NaNs below
        cumw = jnp.cumsum(sw)
        pre = cumw - sw                           # exclusive prefix
        pos = jnp.arange(n)
        sstart = jax.ops.segment_min(pos, sseg, num_segments)
        segbase = pre[jnp.clip(sstart, 0, n - 1)][sseg]
        totw = jax.ops.segment_sum(fw, fseg, num_segments)
        q = (pre - segbase + sw / 2) / jnp.maximum(totw[sseg], 1e-300)
        b = self._kbin(q)
        comb = sseg.astype(jnp.int64) * C + b.astype(jnp.int64)
        nw = jax.ops.segment_sum(sw, comb, num_segments * C)
        nx = jax.ops.segment_sum(sw * sm, comb, num_segments * C)
        nm = jnp.where(nw > 0, nx / jnp.maximum(nw, 1e-300), 0.0)
        return (nm.reshape(num_segments, C), nw.reshape(num_segments, C))

    # -- ungrouped ------------------------------------------------------
    # State: (means (C,), weights (C,), minmax (2,)) — three vectors.
    def update(self, cv: CV, mask):
        zeros = jnp.zeros(mask.shape[0], jnp.int32)
        cols = self.g_update(cv, mask, ScatterGroups(zeros, 1))
        C = self.C
        return (jnp.stack([c[0] for c in cols[:C]]),
                jnp.stack([c[0] for c in cols[C:2 * C]]),
                jnp.stack([cols[2 * C][0], cols[2 * C + 1][0]]))

    def merge(self, s1, s2):
        fm = jnp.concatenate([s1[0], s2[0]])
        fw = jnp.concatenate([s1[1], s2[1]])
        fseg = jnp.zeros(fm.shape[0], jnp.int32)
        nm, nw = self._recompress(fm, fw, fseg, 1)
        mm = jnp.stack([jnp.minimum(s1[2][0], s2[2][0]),
                        jnp.maximum(s1[2][1], s2[2][1])])
        return (nm[0], nw[0], mm)

    def finalize(self, s):
        arrs = list(s)
        ungrouped = len(arrs) == 3 and arrs[0].ndim == 1 \
            and arrs[0].shape[0] == self.C
        C = self.C
        if ungrouped:
            means = arrs[0][None, :]
            ws = arrs[1][None, :]
            vmin, vmax = arrs[2][0][None], arrs[2][1][None]
        else:
            means = jnp.stack(arrs[:C], axis=1)           # (n, C)
            ws = jnp.stack(arrs[C:2 * C], axis=1)
            vmin, vmax = arrs[2 * C], arrs[2 * C + 1]
        n = means.shape[0]
        # all-NaN groups kept vmin at its +inf identity: resolve to vmax
        # (= NaN); a genuine all-+inf group has vmax = +inf and stands
        vmin = jnp.where(jnp.isposinf(vmin)
                         & jnp.logical_not(jnp.isposinf(vmax)),
                         vmax, vmin)
        # compact nonzero centroids to the front (stable: preserves the
        # rank order); empty tail gets mid=+inf so it is never selected
        order = jnp.argsort((ws <= 0).astype(jnp.uint8), axis=1,
                            stable=True)
        cm = jnp.take_along_axis(means, order, axis=1)
        cw = jnp.take_along_axis(ws, order, axis=1)
        nc = jnp.sum((cw > 0).astype(jnp.int32), axis=1)  # (n,)
        totw = jnp.sum(cw, axis=1)
        cumw = jnp.cumsum(cw, axis=1)
        mid = jnp.where(cw > 0, cumw - cw / 2, jnp.inf)   # (n, C)
        outs = []
        for pq in self.percentages:
            t = pq * totw                                  # (n,)
            j = jnp.sum((mid <= t[:, None]).astype(jnp.int32), axis=1)
            jl = jnp.clip(j - 1, 0, C - 1)
            jr = jnp.clip(j, 0, C - 1)
            lm = jnp.where(j > 0,
                           jnp.take_along_axis(cm, jl[:, None],
                                               axis=1)[:, 0], vmin)
            lr = jnp.where(j > 0,
                           jnp.take_along_axis(mid, jl[:, None],
                                               axis=1)[:, 0], 0.0)
            rm = jnp.where(j < nc,
                           jnp.take_along_axis(cm, jr[:, None],
                                               axis=1)[:, 0], vmax)
            rr = jnp.where(j < nc,
                           jnp.take_along_axis(mid, jr[:, None],
                                               axis=1)[:, 0], totw)
            frac = jnp.clip((t - lr) / jnp.maximum(rr - lr, 1e-300),
                            0.0, 1.0)
            # endpoint guards keep a NaN neighbor (NaN sorts greatest,
            # Java Double ordering) from poisoning frac=0/1 answers; an
            # interior frac with a NaN right neighbor snaps to the left
            # centroid — NaN is returned only once t reaches the NaN
            # centroid's own midpoint (docs/compatibility.md)
            mid_v = lm + frac * (rm - lm)
            mid_v = jnp.where(jnp.isnan(rm) & ~jnp.isnan(lm), lm, mid_v)
            outs.append(jnp.where(frac <= 0.0, lm,
                                  jnp.where(frac >= 1.0, rm, mid_v)))
        ok = totw > 0
        if self.scalar_out:
            v = outs[0]
            if ungrouped:
                return v[0], ok[0]
            return v, ok
        P = len(self.percentages)
        flat = jnp.stack(outs, axis=1).reshape(-1)         # (n*P,)
        off = (jnp.arange(n + 1, dtype=jnp.int32) * P)
        child = CV(flat, jnp.ones(n * P, jnp.bool_))
        v = CV(jnp.zeros(0, jnp.int8), jnp.ones(n, jnp.bool_), off,
               (child,))
        if ungrouped:
            return v, ok[0]
        return v, ok

    def __repr__(self):
        return f"percentile_approx({self.child}, {self.percentages})"


class Median(Percentile):
    def __init__(self, child, percentages=0.5, accuracy: int = 10000):
        super().__init__(child, 0.5, accuracy)

    def __repr__(self):
        return f"median({self.child})"
