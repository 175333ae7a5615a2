"""String kernel + expression correctness vs Python references."""
import pyarrow as pa
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.expr.expressions import col, lit
from spark_rapids_tpu.columnar import dtypes as dt

from asserts import assert_rows_equal
from data_gen import IntegerGen, StringGen, gen_df


def _py(at, i=0):
    return at.column(i).to_pylist()


def test_length_upper_lower(session):
    df, at = gen_df(session, [("s", StringGen(max_len=15))], n=800, seed=50)
    out = df.select(F.length(col("s")).alias("l"),
                    F.upper(col("s")).alias("u"),
                    F.lower(col("s")).alias("lo")).to_arrow()
    exp = []
    for s in _py(at):
        if s is None:
            exp.append((None, None, None))
        else:
            # ASCII-only case mapping (documented deviation); test data is
            # mostly ASCII, snowman passes through unchanged
            up = "".join(c.upper() if c.isascii() else c for c in s)
            lo = "".join(c.lower() if c.isascii() else c for c in s)
            exp.append((len(s), up, lo))
    assert_rows_equal(out, exp, ignore_order=False)


def test_substring(session):
    df, at = gen_df(session, [("s", StringGen(max_len=12, charset="abcdef",
                                              no_special=True))],
                    n=500, seed=51)
    out = df.select(F.substring(col("s"), 2, 3).alias("a"),
                    F.substring(col("s"), -2, None).alias("b")).to_arrow()
    exp = []
    for s in _py(at):
        if s is None:
            exp.append((None, None))
        else:
            exp.append((s[1:4], s[-2:] if len(s) >= 2 else s))
    assert_rows_equal(out, exp, ignore_order=False)


def test_concat(session):
    df, at = gen_df(session, [("a", StringGen(max_len=6, charset="xyz")),
                              ("b", StringGen(max_len=6, charset="123"))],
                    n=600, seed=52)
    out = df.select(F.concat(col("a"), lit("-"), col("b")).alias("c"))
    exp = []
    for a, b in zip(_py(at, 0), _py(at, 1)):
        exp.append((None if a is None or b is None else f"{a}-{b}",))
    assert_rows_equal(out.to_arrow(), exp, ignore_order=False)


def test_predicates_contains_starts_ends(session):
    df, at = gen_df(session, [("s", StringGen(max_len=10,
                                              charset="abc"))],
                    n=800, seed=53)
    out = df.select(col("s").contains("ab").alias("c"),
                    col("s").startswith("a").alias("st"),
                    col("s").endswith("bc").alias("en")).to_arrow()
    exp = []
    for s in _py(at):
        if s is None:
            exp.append((None, None, None))
        else:
            exp.append(("ab" in s, s.startswith("a"), s.endswith("bc")))
    assert_rows_equal(out, exp, ignore_order=False)


def test_like(session):
    df, at = gen_df(session, [("s", StringGen(max_len=8, charset="ab%"))],
                    n=500, seed=54)
    import fnmatch
    out = df.filter(col("s").like("a%b")).to_arrow()
    exp = [(s,) for s in _py(at)
           if s is not None and len(s) >= 2 and s.startswith("a")
           and s.endswith("b")]
    assert_rows_equal(out, exp)


def test_string_comparisons(session):
    df, at = gen_df(session, [("a", StringGen(max_len=8, charset="abc")),
                              ("b", StringGen(max_len=8, charset="abc"))],
                    n=900, seed=55)
    out = df.select((col("a") == col("b")).alias("eq"),
                    (col("a") < col("b")).alias("lt"),
                    (col("a") >= col("b")).alias("ge")).to_arrow()
    exp = []
    for a, b in zip(_py(at, 0), _py(at, 1)):
        if a is None or b is None:
            exp.append((None, None, None))
        else:
            exp.append((a == b, a < b, a >= b))
    assert_rows_equal(out, exp, ignore_order=False)


def test_string_compare_literal_filter(session):
    df, at = gen_df(session, [("s", StringGen(max_len=5, charset="mnop"))],
                    n=400, seed=56)
    out = df.filter(col("s") > "n").to_arrow()
    exp = [(s,) for s in _py(at) if s is not None and s > "n"]
    assert_rows_equal(out, exp)


def test_cast_string_to_numbers(session):
    vals = ["42", " -7 ", "3.99", "abc", "", None, "999999999999",
            "  +12", "1e3", "Infinity", "-infinity", "NaN", "12.5e-1"]
    df = session.create_dataframe({"s": pa.array(vals, pa.string())})
    out = df.select(col("s").cast(dt.INT32).alias("i"),
                    col("s").cast(dt.FLOAT64).alias("f"),
                    ).to_arrow().to_pydict()
    assert out["i"] == [42, -7, 3, None, None, None, None, 12, None, None,
                        None, None, None]
    import math
    f = out["f"]
    assert f[0] == 42.0 and f[1] == -7.0 and f[2] == 3.99
    assert f[3] is None and f[4] is None and f[5] is None
    assert f[6] == 999999999999.0
    assert f[8] == 1000.0
    assert f[9] == math.inf and f[10] == -math.inf
    assert math.isnan(f[11])
    assert abs(f[12] - 1.25) < 1e-12


def test_cast_numbers_to_string(session):
    import decimal
    df = session.create_dataframe({
        "i": pa.array([0, -5, 12345, None], pa.int64()),
        "b": pa.array([True, False, None, True]),
        "d": pa.array([decimal.Decimal("1.50"), decimal.Decimal("-0.05"),
                       decimal.Decimal("123.00"), None],
                      pa.decimal128(9, 2)),
    })
    out = df.select(col("i").cast(dt.STRING).alias("si"),
                    col("b").cast(dt.STRING).alias("sb"),
                    col("d").cast(dt.STRING).alias("sd")).to_arrow()
    got = out.to_pydict()
    assert got["si"] == ["0", "-5", "12345", None]
    assert got["sb"] == ["true", "false", None, "true"]
    assert got["sd"] == ["1.50", "-0.05", "123.00", None]


def test_cast_date_to_string(session):
    import datetime
    df = session.create_dataframe({"d": pa.array(
        [datetime.date(1970, 1, 1), datetime.date(2024, 2, 29),
         datetime.date(1969, 12, 31), None], pa.date32())})
    out = df.select(col("d").cast(dt.STRING).alias("s")).to_arrow()
    assert out.column(0).to_pylist() == \
        ["1970-01-01", "2024-02-29", "1969-12-31", None]


def test_string_cast_bool(session):
    df = session.create_dataframe({"s": pa.array(
        ["true", "FALSE", "yes", "0", "maybe", None])})
    out = df.select(col("s").cast(dt.BOOL).alias("b")).to_arrow()
    assert out.column(0).to_pylist() == [True, False, True, False, None,
                                         None]


def test_like_exact_and_cast_wide_ints(session):
    df = session.create_dataframe({"s": ["abc", "abcabc", "ab"],
                                   "i": pa.array([123456789] * 1024
                                                 + [None] * 0,
                                                 pa.int64())[:3]})
    got = df.select(col("s").like("abc").alias("m")).to_arrow()
    assert got.column(0).to_pylist() == [True, False, False]
    # wide ints: 1024 rows of 9-digit numbers must not overflow the buffer
    wide = session.create_dataframe(
        {"i": pa.array([123456789] * 1024, pa.int64())})
    out = wide.select(col("i").cast(dt.STRING).alias("s")).to_arrow()
    assert out.column(0).to_pylist() == ["123456789"] * 1024


def test_float_parse_rejects_long_garbage(session):
    df = session.create_dataframe({"s": ["1" * 40 + "xyz", "2.5"]})
    out = df.select(col("s").cast(dt.FLOAT64).alias("f")).to_arrow()
    assert out.column(0).to_pylist() == [None, 2.5]


def test_float_parse_exponent_validation(session):
    df = session.create_dataframe({"s": ["1e5-3", "2e", "3e+4", "5e-2",
                                         "1e5"]})
    out = df.select(col("s").cast(dt.FLOAT64).alias("f")).to_arrow()
    assert out.column(0).to_pylist() == [None, None, 30000.0, 0.05,
                                         100000.0]


def test_int_parse_19_digit_overflow(session):
    df = session.create_dataframe({"s": [
        "9223372036854775807", "9223372036854775808",
        "-9223372036854775808", "-9223372036854775809"]})
    out = df.select(col("s").cast(dt.INT64).alias("i")).to_arrow()
    assert out.column(0).to_pylist() == [2**63 - 1, None, -2**63, None]


def test_window_via_with_column(session):
    from spark_rapids_tpu.window import Window, row_number
    df = session.create_dataframe({"k": [1, 1, 2], "v": [5, 3, 9]})
    out = df.with_column(
        "rn", row_number().over(Window.partition_by("k").order_by("v")))
    got = sorted(out.collect())
    assert got == [(1, 3, 1), (1, 5, 2), (2, 9, 1)]


def test_like_underscore(session):
    df = session.create_dataframe({"s": ["cat", "cut", "ct", "cart",
                                         "scatter", None]})
    out = df.select(col("s").like("c_t").alias("a"),
                    col("s").like("c_t%").alias("b"),
                    col("s").like("%c_t%").alias("c")).to_arrow()
    got = out.to_pydict()
    assert got["a"] == [True, True, False, False, False, None]
    assert got["b"] == [True, True, False, False, False, None]
    assert got["c"] == [True, True, False, False, True, None]


def test_like_middle_run_not_in_prefix(session):
    df = session.create_dataframe({"s": ["abQQcd", "abXbYcd"]})
    out = df.select(col("s").like("ab%_b%cd").alias("m")).to_arrow()
    # '_b' must occur BETWEEN the 'ab' prefix and 'cd' suffix
    assert out.column(0).to_pylist() == [False, True]


# --- literal-pattern kernels (ops/strings.py) against Python on bytes ----

def _string_column(rows, tail=b"", capacity=None):
    """A string CV holding `rows` (bytes, or None for a null), with `tail`
    after the last offset and zeros up to `capacity` bytes."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.ops.kernel_utils import CV
    body = b"".join(r or b"" for r in rows)
    lens = [len(r or b"") for r in rows]
    buf = (body + tail).ljust(capacity or 0, b"\0")
    return CV(jnp.asarray(np.frombuffer(buf, np.uint8).copy()),
              jnp.asarray([r is not None for r in rows], jnp.bool_),
              jnp.asarray(np.cumsum([0] + lens), jnp.int32))


def _literal_match_columns():
    import random
    rng = random.Random(4242)
    alphabet = [b"g", b"r", b"e", b"n", b"a", b" ", "é".encode(),
                "☃".encode()]
    rows = [None if rng.random() < 0.1 else
            b"".join(rng.choice(alphabet) for _ in range(rng.randrange(13)))
            for _ in range(300)]
    rows += [b"", b"forest green", b"green", "grün".encode(), b"engr",
             b"gr\xc3\xa9en", b"gren", None]
    rng.shuffle(rows)
    return {
        "random": _string_column(rows, tail=b"green" * 8, capacity=4096),
        # a pattern that only spans the boundary of two rows
        "adjacent": _string_column([b"xxgr", b"een", b"gre", b"en", b"g",
                                    b"reen", None, b"green", b"gree"],
                                   tail=b"n"),
        # the pattern only in the buffer past offsets[n]
        "tail": _string_column([b"ab", b"", b"gr"],
                               tail=b"eengreen" + b"green" * 4,
                               capacity=64),
        "empty": _string_column([b"", None, b""]),
    }


def _like_regex(pat):
    import re
    out = []
    for ch in pat.encode():
        out.append(b".*" if ch == ord("%") else
                   b"." if ch == ord("_") else re.escape(bytes([ch])))
    return re.compile(b"".join(out), re.DOTALL)


def _like_reference(pat):
    rx = _like_regex(pat)
    return lambda r: rx.fullmatch(r) is not None


_LONGEST = b"green" * 4  # longer than every row; only in the tails

_LITERAL_MATCH_CASES = [
    ("contains", b"green", lambda p: lambda r: p in r),
    ("contains", b"gre", lambda p: lambda r: p in r),
    ("contains", b"e", lambda p: lambda r: p in r),
    ("contains", "é".encode(), lambda p: lambda r: p in r),
    ("contains", "☃".encode(), lambda p: lambda r: p in r),
    ("contains", b"forest green", lambda p: lambda r: p in r),
    ("contains", _LONGEST, lambda p: lambda r: p in r),
    ("startswith", b"gr", lambda p: lambda r: r.startswith(p)),
    ("startswith", b"green", lambda p: lambda r: r.startswith(p)),
    ("startswith", _LONGEST, lambda p: lambda r: r.startswith(p)),
    ("startswith", b"", lambda p: lambda r: r.startswith(p)),
    ("endswith", b"en", lambda p: lambda r: r.endswith(p)),
    ("endswith", "é".encode(), lambda p: lambda r: r.endswith(p)),
    ("endswith", _LONGEST, lambda p: lambda r: r.endswith(p)),
    ("instr", b"green", lambda p: lambda r: r.find(p) + 1),
    ("instr", b"e", lambda p: lambda r: r.find(p) + 1),
    ("instr", b"", lambda p: lambda r: 1),
    ("instr", _LONGEST, lambda p: lambda r: r.find(p) + 1),
    ("like", "%green%", _like_reference),
    ("like", "%r_e%", _like_reference),
    ("like", "g%e%n", _like_reference),
    ("like", "gr%e%en", _like_reference),
    ("like", "g_e%", _like_reference),
    ("like", "%_n", _like_reference),
    ("like", "gr%_n", _like_reference),
    ("like", "%é%", _like_reference),
    ("like", "g___n", _like_reference),
    ("like", "green", _like_reference),
    ("like", "", _like_reference),
    ("like", "%", _like_reference),
]


@pytest.mark.parametrize(
    "op,pattern,reference", _LITERAL_MATCH_CASES,
    ids=[f"{op}-{p if isinstance(p, str) else p.decode()}-{i}"
         for i, (op, p, _) in enumerate(_LITERAL_MATCH_CASES)])
def test_literal_match_vs_python(op, pattern, reference):
    """contains / startswith / endswith / instr / LIKE per row against
    Python on the row's bytes, over random rows (empty, null, non-ASCII),
    rows a pattern only spans the boundary of, a buffer with the pattern
    past its last offset, and a buffer of no bytes."""
    import jax
    import numpy as np
    from spark_rapids_tpu.expr.string_exprs import Like
    from spark_rapids_tpu.ops import strings as S

    class _Given:  # the column as a child expression of LIKE
        def __init__(self, cv):
            self.cv = cv

        def emit(self, ctx):
            return self.cv

    def run(cv):
        if op == "like":
            return Like(_Given(cv), pattern).emit(None).data
        kernel = {"contains": S.contains, "startswith": S.startswith,
                  "endswith": S.endswith, "instr": S.find_first}[op]
        return kernel(cv, pattern)

    ref = reference(pattern)
    for name, cv in _literal_match_columns().items():
        got = np.asarray(jax.jit(run)(cv))
        off = np.asarray(cv.offsets)
        data = np.asarray(cv.data).tobytes()
        valid = np.asarray(cv.validity)
        for i in range(len(valid)):
            if not valid[i]:
                continue
            row = data[off[i]:off[i + 1]]
            assert got[i] == ref(row), (name, i, row)


@pytest.mark.parametrize("kernel", ["contains", "startswith", "endswith",
                                    "find_first"])
def test_literal_match_gathers_no_byte(kernel):
    """The literal-pattern kernels work on the byte buffer by shifted
    compares and a doubling prefix count: no gather reads or indexes as
    many elements as the buffer has bytes, nothing is scattered, no loop
    carries the bytes (find_first's search loops over the rows), nothing
    is sorted, and no cumsum or associative scan (a minute to compile for
    a v5e at 1 Mi elements) is traced."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.ops import strings as S
    from spark_rapids_tpu.ops.kernel_utils import CV

    n, nbytes = 100, 4096
    fn = getattr(S, kernel)
    jaxpr = jax.make_jaxpr(lambda d, v, o: fn(CV(d, v, o), b"gr_en"))(
        jax.ShapeDtypeStruct((nbytes,), jnp.uint8),
        jax.ShapeDtypeStruct((n,), jnp.bool_),
        jax.ShapeDtypeStruct((n + 1,), jnp.int32))

    def eqns(jx):
        for e in jx.eqns:
            yield e
            for p in e.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from eqns(inner)

    def size(v):
        return int(np.prod(v.aval.shape))

    seen = list(eqns(jaxpr.jaxpr))
    assert seen
    for e in seen:
        name = e.primitive.name
        assert "scatter" not in name, name
        assert name not in ("sort", "cumsum", "cumprod", "cummax",
                            "cummin", "cumlogsumexp"), name
        if name in ("while", "scan"):
            assert all(size(v) < nbytes for v in e.outvars), e
        if name == "gather":
            assert size(e.invars[1]) < nbytes, e
            assert size(e.outvars[0]) < nbytes, e
        if name == "slice":
            assert e.params.get("strides") in (None, (1,)), e
