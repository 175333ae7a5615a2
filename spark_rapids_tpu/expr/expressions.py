"""Expression trees: resolution, Spark type coercion, and traced evaluation.

The analog of the reference's GpuExpression layer (reference:
sql-plugin/.../RapidsMeta.scala:1112 BaseExprMeta; arithmetic.scala,
predicates.scala). Differences, TPU-first:

  - An expression node's `emit(ctx)` runs *inside* a jax trace and returns a
    `CV`; the whole bound tree therefore compiles into one fused XLA program
    instead of a sequence of cudf kernel launches.
  - Binding maps ColumnRef -> BoundRef(ordinal) against an input Schema, like
    the reference's `GpuBindReferences.bindGpuReferences`.

Unsupported expressions raise `UnsupportedExpr` during binding — the planner
catches this and falls back to CPU for the enclosing operator, mirroring
`willNotWorkOnGpu` tagging (RapidsMeta.scala:87).
"""
from __future__ import annotations

import datetime
import decimal
import math
from typing import List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.table import Schema
from ..ops import elementwise as ew
from ..ops.kernel_utils import CV

__all__ = [
    "Expression", "UnsupportedExpr", "EmitCtx", "ColumnRef", "BoundRef",
    "Literal", "Alias", "Add", "Subtract", "Multiply", "Divide", "IntDivide",
    "Remainder", "Pmod", "Negate", "Abs", "Eq", "Ne", "Lt", "Le", "Gt", "Ge",
    "EqNullSafe", "And", "Or", "Not", "IsNull", "IsNotNull", "IsNaN", "Cast",
    "Coalesce", "If", "CaseWhen", "In", "MathUnary", "Round", "Greatest",
    "Least", "lit", "col", "BitwiseAnd", "BitwiseOr", "BitwiseXor",
    "BitwiseNot", "ShiftLeft", "ShiftRight", "Pow", "Atan2",
]


class UnsupportedExpr(Exception):
    """Raised at bind time when an expression cannot run on TPU."""


class EmitCtx:
    """Trace-time context: the input CVs and the batch capacity."""

    def __init__(self, cvs: Sequence[CV], capacity: int):
        self.cvs = list(cvs)
        self.capacity = capacity
        # bound lambda-variable values for higher-order array functions
        # (collection_exprs): var id -> element-domain CV
        self.lambda_vals = {}


class Expression:
    children: List["Expression"] = []
    dtype: Optional[dt.DataType] = None   # set after bind

    def bind(self, schema: Schema) -> "Expression":
        raise NotImplementedError

    def emit(self, ctx: EmitCtx) -> CV:
        raise NotImplementedError

    @property
    def name(self) -> str:
        return str(self)

    # Fluent builder API (the DataFrame `Column` surface).
    def alias(self, name):
        return Alias(self, name)

    def cast(self, dtype):
        return Cast(self, dtype)

    def __add__(self, o):
        return Add(self, _wrap(o))

    def __radd__(self, o):
        return Add(_wrap(o), self)

    def __sub__(self, o):
        return Subtract(self, _wrap(o))

    def __rsub__(self, o):
        return Subtract(_wrap(o), self)

    def __mul__(self, o):
        return Multiply(self, _wrap(o))

    def __rmul__(self, o):
        return Multiply(_wrap(o), self)

    def __truediv__(self, o):
        return Divide(self, _wrap(o))

    def __mod__(self, o):
        return Remainder(self, _wrap(o))

    def __neg__(self):
        return Negate(self)

    def __eq__(self, o):  # type: ignore[override]
        return Eq(self, _wrap(o))

    def __ne__(self, o):  # type: ignore[override]
        return Ne(self, _wrap(o))

    def __lt__(self, o):
        return Lt(self, _wrap(o))

    def __le__(self, o):
        return Le(self, _wrap(o))

    def __gt__(self, o):
        return Gt(self, _wrap(o))

    def __ge__(self, o):
        return Ge(self, _wrap(o))

    def __and__(self, o):
        return And(self, _wrap(o))

    def __or__(self, o):
        return Or(self, _wrap(o))

    def __invert__(self):
        return Not(self)

    def __hash__(self):
        return id(self)

    def isNull(self):
        return IsNull(self)

    def isNotNull(self):
        return IsNotNull(self)

    def isin(self, *values):
        return In(self, [_wrap(v) for v in values])

    def between(self, lo, hi):
        return And(Ge(self, _wrap(lo)), Le(self, _wrap(hi)))

    # string surface (module imported lazily to avoid a cycle)
    def contains(self, pattern):
        from .string_exprs import Contains
        return Contains(self, _wrap(pattern))

    def startswith(self, pattern):
        from .string_exprs import StartsWith
        return StartsWith(self, _wrap(pattern))

    def endswith(self, pattern):
        from .string_exprs import EndsWith
        return EndsWith(self, _wrap(pattern))

    def like(self, pattern: str):
        from .string_exprs import Like
        return Like(self, pattern)

    def rlike(self, pattern: str):
        from .regex_exprs import RLike
        return RLike(self, pattern)

    def substr(self, start, length=None):
        from .string_exprs import Substring
        return Substring(self, start, length)

    def getItem(self, key):
        from .collection_exprs import GetArrayItem
        return GetArrayItem(self, _wrap(key))

    def getField(self, name: str):
        from .collection_exprs import GetStructField
        return GetStructField(self, name)

    def __getitem__(self, key):
        if isinstance(key, str):
            return self.getField(key)
        return self.getItem(key)


def _wrap(v) -> Expression:
    return v if isinstance(v, Expression) else Literal(v)


def col(name: str) -> "ColumnRef":
    return ColumnRef(name)


def lit(v) -> "Literal":
    return Literal(v)


# ----------------------------------------------------------------------
class ColumnRef(Expression):
    def __init__(self, name: str):
        self._name = name
        self.children = []

    @property
    def name(self):
        return self._name

    def bind(self, schema: Schema):
        idx = schema.index_of(self._name)
        return BoundRef(idx, schema[idx].dtype, self._name)

    def __repr__(self):
        return self._name


class BoundRef(Expression):
    def __init__(self, ordinal: int, dtype: dt.DataType, name: str = ""):
        self.ordinal = ordinal
        self.dtype = dtype
        self._name = name or f"c{ordinal}"
        self.children = []

    @property
    def name(self):
        return self._name

    def bind(self, schema):
        return self

    def emit(self, ctx: EmitCtx) -> CV:
        return ctx.cvs[self.ordinal]

    def __repr__(self):
        return f"{self._name}#{self.ordinal}"


def _infer_literal_dtype(v) -> dt.DataType:
    if v is None:
        return dt.NULLTYPE
    if isinstance(v, bool):
        return dt.BOOL
    if isinstance(v, int):
        return dt.INT32 if -2**31 <= v < 2**31 else dt.INT64
    if isinstance(v, float):
        return dt.FLOAT64
    if isinstance(v, str):
        return dt.STRING
    if isinstance(v, bytes):
        return dt.BINARY
    if isinstance(v, decimal.Decimal):
        sign, digits, exp = v.as_tuple()
        scale = max(0, -exp)
        precision = max(len(digits), scale)
        return dt.DecimalType(precision, scale)
    if isinstance(v, datetime.datetime):
        return dt.TIMESTAMP
    if isinstance(v, datetime.date):
        return dt.DATE
    raise UnsupportedExpr(f"cannot infer literal type for {v!r}")


class Literal(Expression):
    def __init__(self, value, dtype: Optional[dt.DataType] = None):
        self.value = value
        self.dtype = dtype or _infer_literal_dtype(value)
        self.children = []

    def bind(self, schema):
        return self

    def device_value(self):
        v, d = self.value, self.dtype
        if v is None:
            return 0
        if isinstance(d, dt.DecimalType):
            return int(decimal.Decimal(v).scaleb(d.scale).to_integral_value(
                rounding=decimal.ROUND_HALF_UP))
        if isinstance(d, dt.DateType):
            return (v - datetime.date(1970, 1, 1)).days
        if isinstance(d, dt.TimestampType):
            ts = v if v.tzinfo else v.replace(tzinfo=datetime.timezone.utc)
            return int(ts.timestamp() * 1_000_000)
        if isinstance(d, (dt.StringType, dt.BinaryType)):
            return v
        return v

    def emit(self, ctx: EmitCtx) -> CV:
        cap = ctx.capacity
        if self.value is None:
            from ..columnar.column import alloc_shape
            np_dt = self.dtype.np_dtype or np.int8
            return CV(jnp.zeros(alloc_shape(self.dtype, cap), np_dt),
                      jnp.zeros(cap, jnp.bool_))
        if isinstance(self.dtype, dt.DecimalType) \
                and self.dtype.is_decimal128:
            u = self.device_value() & ((1 << 128) - 1)
            lo = u & ((1 << 64) - 1)
            hi = u >> 64
            lo = lo - (1 << 64) if lo >= (1 << 63) else lo
            hi = hi - (1 << 64) if hi >= (1 << 63) else hi
            row = jnp.asarray([lo, hi], jnp.int64)
            return CV(jnp.broadcast_to(row, (cap, 2)),
                      jnp.ones(cap, jnp.bool_))
        if isinstance(self.dtype, (dt.StringType, dt.BinaryType)):
            raw = (self.value.encode() if isinstance(self.value, str)
                   else self.value)
            nb = len(raw)
            if nb == 0:
                return CV(jnp.zeros(128, jnp.uint8), jnp.ones(cap, jnp.bool_),
                          jnp.zeros(cap + 1, jnp.int32))
            # tile the bytes so offsets stay monotonic (Arrow invariant)
            tiled = np.tile(np.frombuffer(raw, np.uint8), cap)
            off = (jnp.arange(cap + 1, dtype=jnp.int32) * nb)
            return CV(jnp.asarray(tiled), jnp.ones(cap, jnp.bool_), off)
        return CV(jnp.full(cap, self.device_value(), self.dtype.np_dtype),
                  jnp.ones(cap, jnp.bool_))

    def __repr__(self):
        return repr(self.value)


class Alias(Expression):
    def __init__(self, child: Expression, name: str):
        self.child = child
        self._name = name
        self.children = [child]

    @property
    def name(self):
        return self._name

    def bind(self, schema):
        b = Alias(self.child.bind(schema), self._name)
        b.dtype = b.child.dtype
        return b

    def emit(self, ctx):
        return self.child.emit(ctx)

    def __repr__(self):
        return f"{self.child} AS {self._name}"


def d128_nodes(exprs) -> int:
    """How many nodes of the bound trees `exprs` compute in 128-bit
    decimals: every node with operands (an Alias only names its child)
    whose own type or an operand's is a decimal past 18 digits, so its
    emit runs the limb arithmetic of ops/decimal128.py for every row.
    What `d128Exprs` counts and what marks a program's name `_d128`."""
    def wide(e):
        return isinstance(e.dtype, dt.DecimalType) and e.dtype.is_decimal128
    n, todo = 0, list(exprs)
    while todo:
        e = todo.pop()
        todo.extend(e.children)
        if e.children and not isinstance(e, Alias):
            n += wide(e) or any(wide(c) for c in e.children)
    return n


# ----------------------------------------------------------------------
# Implicit cast insertion (Spark's binary-op type coercion)
# ----------------------------------------------------------------------
def _coerce_pair(l: Expression, r: Expression, for_division=False):
    lt_, rt = l.dtype, r.dtype
    if isinstance(lt_, dt.NullType):
        l = Cast.bound(l, rt)
        lt_ = rt
    if isinstance(rt, dt.NullType):
        r = Cast.bound(r, lt_)
        rt = lt_
    if isinstance(lt_, dt.DecimalType) or isinstance(rt, dt.DecimalType):
        return _coerce_decimal(l, r, for_division)
    if for_division:
        if not lt_.is_floating:
            l = Cast.bound(l, dt.FLOAT64)
        if not rt.is_floating:
            r = Cast.bound(r, dt.FLOAT64)
        lt_, rt = l.dtype, r.dtype
    if lt_ == rt:
        return l, r, lt_
    out = dt.promote(lt_, rt)
    if lt_ != out:
        l = Cast.bound(l, out)
    if rt != out:
        r = Cast.bound(r, out)
    return l, r, out


def _coerce_decimal(l, r, for_division):
    # decimal op decimal/integral: Spark's implicit coercion; results over
    # precision 18 run on the exact decimal128 kernels.
    def as_dec(e):
        if isinstance(e.dtype, dt.DecimalType):
            return e
        if e.dtype.is_integral:
            # Spark: Byte->dec(3,0) Short->dec(5,0) Int->dec(10,0)
            # Long->dec(20,0)
            p = {1: 3, 2: 5, 4: 10, 8: 20}[e.dtype.np_dtype.itemsize]
            return Cast.bound(e, dt.DecimalType(p, 0))
        raise UnsupportedExpr(f"decimal with {e.dtype}")
    if l.dtype.is_floating or r.dtype.is_floating:
        return (Cast.bound(l, dt.FLOAT64), Cast.bound(r, dt.FLOAT64),
                dt.FLOAT64)
    l, r = as_dec(l), as_dec(r)
    return l, r, None  # result dtype decided per-op


class _BinaryOp(Expression):
    symbol = "?"

    def __init__(self, left: Expression, right: Expression):
        self.left, self.right = left, right
        self.children = [left, right]

    def bind(self, schema):
        b = type(self)(self.left.bind(schema), self.right.bind(schema))
        b._resolve_type()
        return b

    def _resolve_type(self):
        raise NotImplementedError

    def __repr__(self):
        return f"({self.left} {self.symbol} {self.right})"


def _dec_scale_shift(cv: CV, shift: int) -> CV:
    if shift == 0:
        return cv
    return CV(cv.data * (10 ** shift), cv.validity)


def _reject_d128(dtype, what: str):
    """Gate for operators not yet wired to the two-limb kernels: a
    decimal128 column through a plain elementwise kernel would silently
    corrupt (1-D math over [cap,2] limb pairs)."""
    if isinstance(dtype, dt.DecimalType) and dtype.is_decimal128:
        raise UnsupportedExpr(
            f"{what} over decimal precision > 18 not yet implemented")


def _adjust_precision_scale(p: int, s: int):
    """Spark DecimalType.adjustPrecisionScale: clamp precision at 38,
    sacrificing scale down to a floor of min(s, 6)."""
    if p <= 38:
        return p, s
    int_digits = p - s
    min_scale = min(s, 6)
    adjusted = max(38 - int_digits, min_scale)
    return 38, adjusted


def _as_dec128(cv: CV, dtype) -> CV:
    """Widen a decimal64 CV to the [cap,2] limb layout (no-op for 128)."""
    if dtype.is_decimal128:
        return cv
    from ..ops.decimal128 import dec_from_i64
    return CV(dec_from_i64(cv.data), cv.validity)


class _Arith(_BinaryOp):
    kernel = None
    dec128_fn = None    # d128.dec_add / dec_sub

    def _resolve_type(self):
        self.left, self.right, out = _coerce_pair(self.left, self.right)
        if out is None:  # decimal
            p1, s1 = self.left.dtype.precision, self.left.dtype.scale
            p2, s2 = self.right.dtype.precision, self.right.dtype.scale
            s = max(s1, s2)
            p = max(p1 - s1, p2 - s2) + s + 1
            p, s = _adjust_precision_scale(p, s)
            self.dtype = dt.DecimalType(p, s)
        else:
            self.dtype = out

    def emit(self, ctx):
        l, r = self.left.emit(ctx), self.right.emit(ctx)
        if isinstance(self.dtype, dt.DecimalType):
            s = self.dtype.scale
            if self.dtype.is_decimal128:
                # exact 128-bit two-limb path (JNI DecimalUtils analog)
                from ..ops import decimal128 as d128
                ld = _as_dec128(l, self.left.dtype)
                rd = _as_dec128(r, self.right.dtype)
                la, o1 = d128.dec_rescale(ld.data, self.left.dtype.scale,
                                          s, 38)
                ra, o2 = d128.dec_rescale(rd.data, self.right.dtype.scale,
                                          s, 38)
                res, o3 = type(self).dec128_fn(la, ra)
                ok = d128.fits_precision(d128.to_limbs(res),
                                         self.dtype.precision)
                valid = (l.validity & r.validity & ~o1 & ~o2 & ~o3 & ok)
                return CV(res, valid)
            l = _dec_scale_shift(l, s - self.left.dtype.scale)
            r = _dec_scale_shift(r, s - self.right.dtype.scale)
        return type(self).kernel(l, r)


class Add(_Arith):
    symbol = "+"
    kernel = staticmethod(ew.add)

    @staticmethod
    def dec128_fn(a, b):
        from ..ops.decimal128 import dec_add
        return dec_add(a, b)


class Subtract(_Arith):
    symbol = "-"
    kernel = staticmethod(ew.sub)

    @staticmethod
    def dec128_fn(a, b):
        from ..ops.decimal128 import dec_sub
        return dec_sub(a, b)


class Multiply(_BinaryOp):
    symbol = "*"

    def _resolve_type(self):
        self.left, self.right, out = _coerce_pair(self.left, self.right)
        if out is None:
            p1, s1 = self.left.dtype.precision, self.left.dtype.scale
            p2, s2 = self.right.dtype.precision, self.right.dtype.scale
            p, s = _adjust_precision_scale(p1 + p2 + 1, s1 + s2)
            self._full_scale = s1 + s2
            self.dtype = dt.DecimalType(p, s)
        else:
            self.dtype = out

    def emit(self, ctx):
        l, r = self.left.emit(ctx), self.right.emit(ctx)
        if isinstance(self.dtype, dt.DecimalType) \
                and self.dtype.is_decimal128:
            from ..ops import decimal128 as d128
            ld = _as_dec128(l, self.left.dtype)
            rd = _as_dec128(r, self.right.dtype)
            res, ovf = d128.dec_mul_scaled(
                ld.data, rd.data, self._full_scale - self.dtype.scale,
                self.dtype.precision)
            return CV(res, l.validity & r.validity & ~ovf)
        return ew.mul(l, r)


class Divide(_BinaryOp):
    symbol = "/"

    def _resolve_type(self):
        self.left, self.right, out = _coerce_pair(self.left, self.right,
                                                  for_division=True)
        if out is None:
            # Spark decimal division result type, exact 128-bit long
            # division with HALF_UP (JNI DecimalUtils.divide128 analog)
            p1, s1 = self.left.dtype.precision, self.left.dtype.scale
            p2, s2 = self.right.dtype.precision, self.right.dtype.scale
            s = max(6, s1 + p2 + 1)
            p = p1 - s1 + s2 + s
            p, s = _adjust_precision_scale(p, s)
            self.dtype = dt.DecimalType(p, s)
        else:
            self.dtype = out

    def emit(self, ctx):
        l, r = self.left.emit(ctx), self.right.emit(ctx)
        if isinstance(self.dtype, dt.DecimalType):
            from ..ops import decimal128 as d128
            s = self.dtype.scale
            shift = s - self.left.dtype.scale + self.right.dtype.scale
            ld = _as_dec128(l, self.left.dtype)
            rd = _as_dec128(r, self.right.dtype)
            res, ovf, divzero = d128.dec_div(
                ld.data, rd.data, shift, self.dtype.precision,
                num_digits=self.left.dtype.precision)
            valid = ew.and_validity(l, r) & ~ovf & ~divzero
            if self.dtype.is_decimal128:
                return CV(res, valid)
            v64, fits = d128.dec_to_i64(res)
            return CV(v64, valid & fits)
        return ew.divide(l, r)


class IntDivide(_BinaryOp):
    symbol = "div"

    def _resolve_type(self):
        self.left, self.right, out = _coerce_pair(self.left, self.right)
        if out is None or not out.is_integral:
            if out is None:
                _reject_d128(self.left.dtype, "div")
                _reject_d128(self.right.dtype, "div")
                self.dtype = dt.INT64
                return
            raise UnsupportedExpr("div on non-integral")
        self.dtype = dt.INT64

    def emit(self, ctx):
        l, r = self.left.emit(ctx), self.right.emit(ctx)
        if isinstance(self.left.dtype, dt.DecimalType):
            s1, s2 = self.left.dtype.scale, self.right.dtype.scale
            s = max(s1, s2)
            l = _dec_scale_shift(l, s - s1)
            r = _dec_scale_shift(r, s - s2)
        out = ew.int_divide(l, r)
        return CV(out.data.astype(jnp.int64), out.validity)


class Remainder(_BinaryOp):
    symbol = "%"

    def _resolve_type(self):
        self.left, self.right, out = _coerce_pair(self.left, self.right)
        if out is None:
            _reject_d128(self.left.dtype, "remainder")
            _reject_d128(self.right.dtype, "remainder")
            s = max(self.left.dtype.scale, self.right.dtype.scale)
            p = min(18, max(self.left.dtype.precision,
                            self.right.dtype.precision))
            self.dtype = dt.DecimalType(p, s)
        else:
            self.dtype = out

    def emit(self, ctx):
        l, r = self.left.emit(ctx), self.right.emit(ctx)
        if isinstance(self.dtype, dt.DecimalType):
            s = self.dtype.scale
            l = _dec_scale_shift(l, s - self.left.dtype.scale)
            r = _dec_scale_shift(r, s - self.right.dtype.scale)
        return ew.remainder(l, r)


class Pmod(Remainder):
    symbol = "pmod"

    def emit(self, ctx):
        l, r = self.left.emit(ctx), self.right.emit(ctx)
        if isinstance(self.dtype, dt.DecimalType):
            s = self.dtype.scale
            l = _dec_scale_shift(l, s - self.left.dtype.scale)
            r = _dec_scale_shift(r, s - self.right.dtype.scale)
        return ew.pmod(l, r)


class _UnaryOp(Expression):
    def __init__(self, child: Expression):
        self.child = child
        self.children = [child]

    def bind(self, schema):
        b = type(self)(self.child.bind(schema))
        b._resolve_type()
        return b

    def _resolve_type(self):
        self.dtype = self.child.dtype


class Negate(_UnaryOp):
    def _resolve_type(self):
        _reject_d128(self.child.dtype, "negate")
        self.dtype = self.child.dtype

    def emit(self, ctx):
        return ew.negate(self.child.emit(ctx))

    def __repr__(self):
        return f"(- {self.child})"


class Abs(_UnaryOp):
    def _resolve_type(self):
        _reject_d128(self.child.dtype, "abs")
        self.dtype = self.child.dtype

    def emit(self, ctx):
        return ew.abs_(self.child.emit(ctx))

    def __repr__(self):
        return f"abs({self.child})"


class _Comparison(_BinaryOp):
    kernel = None
    cmp_op = None   # for string compares: applied to sign(-1/0/1)

    def _resolve_type(self):
        lt_, rt = self.left.dtype, self.right.dtype
        l_str = isinstance(lt_, (dt.StringType, dt.BinaryType))
        r_str = isinstance(rt, (dt.StringType, dt.BinaryType))
        if l_str != r_str:
            raise UnsupportedExpr("string/non-string compare")
        if not l_str and lt_ != rt:
            self.left, self.right, _ = _coerce_pair(self.left, self.right)
        self.dtype = dt.BOOL

    def emit(self, ctx):
        # literal string equality: chunked compare, not the byte-domain
        # walk (ops.strings.equals_literal)
        if (isinstance(self.left.dtype, (dt.StringType, dt.BinaryType))
                and type(self) in (Eq, Ne)):
            lit = col = None
            if isinstance(self.right, Literal):
                lit, col = self.right, self.left
            elif isinstance(self.left, Literal):
                lit, col = self.left, self.right
            if lit is not None and isinstance(lit.value, (str, bytes)):
                from ..ops import strings as ops_str
                cv = col.emit(ctx)
                raw = (lit.value.encode() if isinstance(lit.value, str)
                       else lit.value)
                eq = ops_str.equals_literal(cv, raw)
                if type(self) is Ne:
                    eq = jnp.logical_not(eq)
                return CV(eq, cv.validity)
        l, r = self.left.emit(ctx), self.right.emit(ctx)
        if isinstance(self.left.dtype, (dt.StringType, dt.BinaryType)):
            from ..ops import strings as ops_str
            c = ops_str.compare(l, r)
            return CV(type(self).cmp_op(c), ew.and_validity(l, r))
        if isinstance(self.left.dtype, dt.DecimalType):
            lt_, rt = self.left.dtype, self.right.dtype
            if lt_.is_decimal128 or rt.is_decimal128:
                from ..ops.decimal128 import dec_cmp_scaled
                ld = _as_dec128(l, lt_)
                rd = _as_dec128(r, rt)
                c = dec_cmp_scaled(ld.data, lt_.scale, rd.data, rt.scale)
                return CV(type(self).cmp_op(c), ew.and_validity(l, r))
            s = max(lt_.scale, rt.scale)
            l = _dec_scale_shift(l, s - lt_.scale)
            r = _dec_scale_shift(r, s - rt.scale)
        return type(self).kernel(l, r)


class Eq(_Comparison):
    symbol = "="
    kernel = staticmethod(ew.eq)
    cmp_op = staticmethod(lambda c: c == 0)


class Ne(_Comparison):
    symbol = "!="
    kernel = staticmethod(ew.ne)
    cmp_op = staticmethod(lambda c: c != 0)


class Lt(_Comparison):
    symbol = "<"
    kernel = staticmethod(ew.lt)
    cmp_op = staticmethod(lambda c: c < 0)


class Le(_Comparison):
    symbol = "<="
    kernel = staticmethod(ew.le)
    cmp_op = staticmethod(lambda c: c <= 0)


class Gt(_Comparison):
    symbol = ">"
    kernel = staticmethod(ew.gt)
    cmp_op = staticmethod(lambda c: c > 0)


class Ge(_Comparison):
    symbol = ">="
    kernel = staticmethod(ew.ge)
    cmp_op = staticmethod(lambda c: c >= 0)


class EqNullSafe(_Comparison):
    symbol = "<=>"
    kernel = staticmethod(ew.eq_null_safe)

    def emit(self, ctx):
        l, r = self.left.emit(ctx), self.right.emit(ctx)
        if isinstance(self.left.dtype, (dt.StringType, dt.BinaryType)):
            from ..ops import strings as ops_str
            c = ops_str.compare(l, r)
            both_null = ~l.validity & ~r.validity
            both_valid = l.validity & r.validity
            out = both_null | (both_valid & (c == 0))
            return CV(out, jnp.ones_like(out))
        return super().emit(ctx)


class And(_BinaryOp):
    symbol = "AND"

    def _resolve_type(self):
        self.dtype = dt.BOOL

    def emit(self, ctx):
        return ew.logical_and(self.left.emit(ctx), self.right.emit(ctx))


class Or(_BinaryOp):
    symbol = "OR"

    def _resolve_type(self):
        self.dtype = dt.BOOL

    def emit(self, ctx):
        return ew.logical_or(self.left.emit(ctx), self.right.emit(ctx))


class Not(_UnaryOp):
    def _resolve_type(self):
        self.dtype = dt.BOOL

    def emit(self, ctx):
        return ew.logical_not(self.child.emit(ctx))

    def __repr__(self):
        return f"NOT {self.child}"


class IsNull(_UnaryOp):
    def _resolve_type(self):
        self.dtype = dt.BOOL

    def emit(self, ctx):
        return ew.is_null(self.child.emit(ctx))

    def __repr__(self):
        return f"({self.child} IS NULL)"


class IsNotNull(_UnaryOp):
    def _resolve_type(self):
        self.dtype = dt.BOOL

    def emit(self, ctx):
        return ew.is_not_null(self.child.emit(ctx))

    def __repr__(self):
        return f"({self.child} IS NOT NULL)"


class IsNaN(_UnaryOp):
    def _resolve_type(self):
        self.dtype = dt.BOOL

    def emit(self, ctx):
        return ew.is_nan(self.child.emit(ctx))


class Cast(Expression):
    """Spark CAST. Full string<->numeric semantics live in ops/cast.py;
    numeric/temporal casts are inline here."""

    def __init__(self, child: Expression, to, ansi=False):
        self.child = child
        if isinstance(to, str):
            to = dt.from_name(to)   # pyspark-style .cast("bigint")
        self.to = to
        self.ansi = ansi
        self.children = [child]

    @staticmethod
    def bound(child: Expression, to: dt.DataType) -> "Cast":
        c = Cast(child, to)
        c.dtype = to
        return c

    def bind(self, schema):
        b = Cast(self.child.bind(schema), self.to, self.ansi)
        b.dtype = self.to
        from_t = b.child.dtype
        str_src_ok = (isinstance(from_t, dt.StringType)
                      and (self.to.is_numeric
                           or isinstance(self.to, (dt.BooleanType,
                                                   dt.DateType,
                                                   dt.TimestampType))))
        str_dst_ok = (isinstance(self.to, dt.StringType)
                      and (from_t.is_integral
                           or isinstance(from_t, (dt.BooleanType,
                                                  dt.DecimalType,
                                                  dt.DateType,
                                                  dt.TimestampType))))
        ok = (from_t == self.to or
              (from_t.is_numeric and self.to.is_numeric) or
              isinstance(from_t, dt.NullType) or
              (isinstance(from_t, dt.BooleanType) and self.to.is_numeric) or
              (from_t.is_numeric and isinstance(self.to, dt.BooleanType)) or
              (isinstance(from_t, dt.TimestampType)
               and (self.to.is_numeric
                    or isinstance(self.to, dt.DateType))) or
              (isinstance(from_t, dt.DateType)
               and isinstance(self.to, (dt.TimestampType, dt.IntegerType))) or
              (from_t.is_numeric
               and isinstance(self.to, dt.TimestampType)) or
              str_src_ok or str_dst_ok)
        if not ok:
            raise UnsupportedExpr(f"cast {from_t} -> {self.to}")
        return b

    def emit(self, ctx):
        from ..ops import cast as cast_ops
        from ..ops import cast_strings as cs
        cv = self.child.emit(ctx)
        from_t = self.child.dtype
        if isinstance(from_t, dt.StringType) and not isinstance(
                self.to, dt.StringType):
            if self.to.is_integral:
                return cs.string_to_int(cv, self.to)
            if self.to.is_floating:
                out = cs.string_to_float(cv)
                return CV(out.data.astype(self.to.np_dtype), out.validity)
            if isinstance(self.to, dt.BooleanType):
                return cs.string_to_bool(cv)
            if isinstance(self.to, dt.DateType):
                return cs.string_to_date(cv)
            if isinstance(self.to, dt.TimestampType):
                return cs.string_to_timestamp(cv)
            if isinstance(self.to, dt.DecimalType):
                return cs.string_to_decimal(cv, self.to)
        if isinstance(self.to, dt.StringType) and not isinstance(
                from_t, dt.StringType):
            if isinstance(from_t, dt.NullType):
                return CV(jnp.zeros(128, jnp.uint8),
                          jnp.zeros(cv.capacity, jnp.bool_),
                          jnp.zeros(cv.capacity + 1, jnp.int32))
            if isinstance(from_t, dt.BooleanType):
                return cs.bool_to_string(cv)
            if isinstance(from_t, dt.DecimalType):
                return cs.decimal_to_string(cv, from_t.scale)
            if isinstance(from_t, dt.DateType):
                return cs.date_to_string(cv)
            if isinstance(from_t, dt.TimestampType):
                return cs.timestamp_to_string(cv)
            if from_t.is_integral:
                return cs.int_to_string(cv)
            raise UnsupportedExpr(f"cast {from_t} -> string")
        return cast_ops.cast_cv(cv, from_t, self.to)

    def __repr__(self):
        return f"CAST({self.child} AS {self.to})"


def _select_cv(pick_a, a: CV, b: CV, out_valid) -> CV:
    """Row-wise select between two CVs; handles var-width via a gather
    over the concatenation of both buffers."""
    if a.offsets is not None or b.offsets is not None:
        from ..ops.concat import concat_cvs
        from ..ops.gather import take_strings
        combined = concat_cvs([a, b], dt.STRING)
        cap = pick_a.shape[0]
        idx = jnp.where(pick_a, jnp.arange(cap), cap + jnp.arange(cap))
        out = take_strings(combined, idx.astype(jnp.int32))
        return CV(out.data, out_valid, out.offsets)
    return CV(jnp.where(pick_a, a.data, b.data), out_valid)


class Coalesce(Expression):
    def __init__(self, *children: Expression):
        self.children = list(children)

    def bind(self, schema):
        bc = [c.bind(schema) for c in self.children]
        out = next((c.dtype for c in bc
                    if not isinstance(c.dtype, dt.NullType)), dt.NULLTYPE)
        bc = [c if c.dtype == out else Cast.bound(c, out) for c in bc]
        b = Coalesce(*bc)
        b.dtype = out
        return b

    def emit(self, ctx):
        cvs = [c.emit(ctx) for c in self.children]
        out = cvs[-1]
        for cv in reversed(cvs[:-1]):
            out = _select_cv(cv.validity, cv, out, cv.validity | out.validity)
        return out

    def __repr__(self):
        return "coalesce(" + ", ".join(map(repr, self.children)) + ")"


class If(Expression):
    def __init__(self, pred: Expression, t: Expression, f: Expression):
        self.pred, self.t, self.f = pred, t, f
        self.children = [pred, t, f]

    def bind(self, schema):
        p, t, f = (c.bind(schema) for c in self.children)
        out = t.dtype if not isinstance(t.dtype, dt.NullType) else f.dtype
        if t.dtype != out:
            t = Cast.bound(t, out)
        if f.dtype != out:
            f = Cast.bound(f, out)
        b = If(p, t, f)
        b.dtype = out
        return b

    def emit(self, ctx):
        p, t, f = (c.emit(ctx) for c in self.children)
        take_t = p.validity & p.data.astype(jnp.bool_)
        out_valid = jnp.where(take_t, t.validity, f.validity)
        return _select_cv(take_t, t, f, out_valid)

    def __repr__(self):
        return f"if({self.pred}, {self.t}, {self.f})"


class CaseWhen(Expression):
    """CASE WHEN p1 THEN v1 ... [ELSE d] END, built as nested If at bind."""

    def __init__(self, branches, default: Optional[Expression] = None):
        self.branches = branches
        self.default = default
        self.children = ([e for p, v in branches for e in (p, v)]
                         + ([default] if default else []))

    def bind(self, schema):
        expr: Expression = self.default or Literal(None)
        for p, v in reversed(self.branches):
            expr = If(p, v, expr)
        return expr.bind(schema)

    def __repr__(self):
        return "CASE WHEN ..."


class In(Expression):
    def __init__(self, child: Expression, values: List[Expression]):
        self.child = child
        self.values = values
        self.children = [child] + values

    def bind(self, schema):
        expr: Expression = None
        for v in self.values:
            e = Eq(self.child, v)
            expr = e if expr is None else Or(expr, e)
        return (expr or Literal(False)).bind(schema)

    def __repr__(self):
        return f"{self.child} IN (...)"


_MATH_FNS = {
    "sqrt": jnp.sqrt, "exp": jnp.exp, "log": jnp.log, "log10": jnp.log10,
    "log2": jnp.log2, "log1p": jnp.log1p, "sin": jnp.sin, "cos": jnp.cos,
    "tan": jnp.tan, "asin": jnp.arcsin, "acos": jnp.arccos,
    "atan": jnp.arctan, "sinh": jnp.sinh, "cosh": jnp.cosh,
    "tanh": jnp.tanh, "cbrt": jnp.cbrt, "expm1": jnp.expm1,
    "floor": jnp.floor, "ceil": jnp.ceil, "signum": jnp.sign,
    "rint": jnp.rint, "degrees": jnp.degrees, "radians": jnp.radians,
}


class MathUnary(_UnaryOp):
    """Double-valued unary math fn with Spark semantics (log(<=0) -> null)."""

    def __init__(self, fn_name: str, child: Expression):
        super().__init__(child)
        self.fn_name = fn_name
        if fn_name not in _MATH_FNS:
            raise UnsupportedExpr(f"math fn {fn_name}")

    def bind(self, schema):
        b = MathUnary(self.fn_name, self.child.bind(schema))
        if not (b.child.dtype.is_numeric or isinstance(b.child.dtype,
                                                       dt.NullType)):
            raise UnsupportedExpr(f"{self.fn_name} on {b.child.dtype}")
        if b.fn_name in ("floor", "ceil") and b.child.dtype.is_integral:
            b.dtype = dt.INT64
        else:
            b.dtype = dt.FLOAT64
        return b

    def emit(self, ctx):
        cv = self.child.emit(ctx)
        x = cv.data.astype(jnp.float64)
        if isinstance(self.child.dtype, dt.DecimalType):
            x = x / (10.0 ** self.child.dtype.scale)
        valid = cv.validity
        if self.fn_name in ("log", "log10", "log2"):
            valid = valid & (x > 0)
            x = jnp.where(x > 0, x, 1.0)
        if self.fn_name == "log1p":
            valid = valid & (x > -1)
            x = jnp.where(x > -1, x, 0.0)
        out = _MATH_FNS[self.fn_name](x)
        if self.dtype == dt.INT64:
            out = out.astype(jnp.int64)
        return CV(out, valid)

    def __repr__(self):
        return f"{self.fn_name}({self.child})"


class Round(Expression):
    """round(x, d) half-up (Spark ROUND)."""

    def __init__(self, child: Expression, digits: int = 0):
        self.child = child
        self.digits = digits
        self.children = [child]

    def bind(self, schema):
        b = Round(self.child.bind(schema), self.digits)
        ct = b.child.dtype
        _reject_d128(ct, "round")
        if isinstance(ct, dt.DecimalType):
            b.dtype = dt.DecimalType(ct.precision,
                                     min(ct.scale, max(self.digits, 0)))
        elif ct.is_integral:
            b.dtype = ct
        else:
            b.dtype = dt.FLOAT64
        return b

    def emit(self, ctx):
        cv = self.child.emit(ctx)
        ct = self.child.dtype
        if isinstance(ct, dt.DecimalType):
            # round HALF_UP at decimal position `digits` (may be negative)
            drop = ct.scale - max(self.digits, 0)
            out = cv.data
            if drop > 0:
                p = 10 ** drop
                half = p // 2
                adj = jnp.where(out >= 0, out + half, out - half)
                q = adj // p
                r = adj - q * p
                out = jnp.where((r != 0) & (adj < 0), q + 1, q)
            if self.digits < 0:
                p = 10 ** (-self.digits)
                half = p // 2
                adj = jnp.where(out >= 0, out + half, out - half)
                q = adj // p
                r = adj - q * p
                q = jnp.where((r != 0) & (adj < 0), q + 1, q)
                out = q * p
            return CV(out, cv.validity)
        if ct.is_integral and self.digits >= 0:
            return cv
        if ct.is_integral:  # negative digits on ints: round at 10^-d
            p = 10 ** (-self.digits)
            half = p // 2
            x = cv.data.astype(jnp.int64)
            adj = jnp.where(x >= 0, x + half, x - half)
            q = adj // p
            r = adj - q * p
            q = jnp.where((r != 0) & (adj < 0), q + 1, q)
            return CV((q * p).astype(ct.np_dtype), cv.validity)
        x = cv.data.astype(jnp.float64)
        p = 10.0 ** self.digits
        scaled = x * p
        out = jnp.where(scaled >= 0, jnp.floor(scaled + 0.5),
                        jnp.ceil(scaled - 0.5)) / p
        if self.dtype.is_integral:
            out = out.astype(ct.np_dtype)
        return CV(out, cv.validity)

    def __repr__(self):
        return f"round({self.child}, {self.digits})"


class _MinMaxOf(Expression):
    is_greatest = True

    def __init__(self, *children: Expression):
        self.children = list(children)

    def bind(self, schema):
        bc = [c.bind(schema) for c in self.children]
        out = bc[0].dtype
        for c in bc[1:]:
            out = dt.promote(out, c.dtype) if c.dtype != out else out
        _reject_d128(out, "greatest/least")
        bc = [c if c.dtype == out else Cast.bound(c, out) for c in bc]
        b = type(self)(*bc)
        b.dtype = out
        return b

    def emit(self, ctx):
        cvs = [c.emit(ctx) for c in self.children]
        out = cvs[0]
        for cv in cvs[1:]:
            if self.is_greatest:
                pick = (~out.validity |
                        (cv.validity & ew._nan_lt(out.data, cv.data)))
            else:
                pick = (~out.validity |
                        (cv.validity & ew._nan_lt(cv.data, out.data)))
            pick = pick & cv.validity
            out = CV(jnp.where(pick, cv.data, out.data),
                     out.validity | cv.validity)
        return out


class _Bitwise(_BinaryOp):
    op = None

    def _resolve_type(self):
        self.left, self.right, out = _coerce_pair(self.left, self.right)
        if out is None or not out.is_integral:
            raise UnsupportedExpr("bitwise op on non-integral")
        self.dtype = out

    def emit(self, ctx):
        l, r = self.left.emit(ctx), self.right.emit(ctx)
        return CV(type(self).op(l.data, r.data), ew.and_validity(l, r))


class BitwiseAnd(_Bitwise):
    symbol = "&"
    op = staticmethod(jnp.bitwise_and)


class BitwiseOr(_Bitwise):
    symbol = "|"
    op = staticmethod(jnp.bitwise_or)


class BitwiseXor(_Bitwise):
    symbol = "^"
    op = staticmethod(jnp.bitwise_xor)


class BitwiseNot(_UnaryOp):
    def _resolve_type(self):
        if not self.child.dtype.is_integral:
            raise UnsupportedExpr("~ on non-integral")
        self.dtype = self.child.dtype

    def emit(self, ctx):
        cv = self.child.emit(ctx)
        return CV(jnp.bitwise_not(cv.data), cv.validity)


class ShiftLeft(_BinaryOp):
    symbol = "<<"

    def _resolve_type(self):
        if not (self.left.dtype.is_integral
                and self.right.dtype.is_integral):
            raise UnsupportedExpr("shift on non-integral")
        # Spark promotes byte/short to int before shifting (mask by 31)
        if isinstance(self.left.dtype, (dt.ByteType, dt.ShortType)):
            self.left = Cast.bound(self.left, dt.INT32)
        self.dtype = self.left.dtype

    def emit(self, ctx):
        l, r = self.left.emit(ctx), self.right.emit(ctx)
        nbits = l.data.dtype.itemsize * 8
        sh = (r.data.astype(jnp.int32) % nbits)  # Java masks the shift
        return CV(l.data << sh.astype(l.data.dtype),
                  ew.and_validity(l, r))


class ShiftRight(ShiftLeft):
    symbol = ">>"

    def emit(self, ctx):
        l, r = self.left.emit(ctx), self.right.emit(ctx)
        nbits = l.data.dtype.itemsize * 8
        sh = (r.data.astype(jnp.int32) % nbits)
        return CV(l.data >> sh.astype(l.data.dtype),
                  ew.and_validity(l, r))


class Pow(_BinaryOp):
    symbol = "pow"

    def _resolve_type(self):
        self.left = (self.left if self.left.dtype.is_floating
                     else Cast.bound(self.left, dt.FLOAT64))
        self.right = (self.right if self.right.dtype.is_floating
                      else Cast.bound(self.right, dt.FLOAT64))
        self.dtype = dt.FLOAT64

    def emit(self, ctx):
        l, r = self.left.emit(ctx), self.right.emit(ctx)
        return CV(jnp.power(l.data.astype(jnp.float64),
                            r.data.astype(jnp.float64)),
                  ew.and_validity(l, r))


class Atan2(_BinaryOp):
    symbol = "atan2"

    def _resolve_type(self):
        for side in ("left", "right"):
            e = getattr(self, side)
            if not (e.dtype.is_numeric or isinstance(e.dtype, dt.NullType)):
                raise UnsupportedExpr(f"atan2 on {e.dtype}")
            if not e.dtype.is_floating:
                setattr(self, side, Cast.bound(e, dt.FLOAT64))
        self.dtype = dt.FLOAT64

    def emit(self, ctx):
        l, r = self.left.emit(ctx), self.right.emit(ctx)
        return CV(jnp.arctan2(l.data.astype(jnp.float64),
                              r.data.astype(jnp.float64)),
                  ew.and_validity(l, r))


class Greatest(_MinMaxOf):
    is_greatest = True


class Least(_MinMaxOf):
    is_greatest = False
