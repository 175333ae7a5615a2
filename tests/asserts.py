"""Result-equality asserts — the dual-run harness core
(reference: integration_tests/src/main/python/asserts.py:693
assert_gpu_and_cpu_are_equal_collect)."""
from __future__ import annotations

import math


def _canon(v, approx):
    if v is None:
        return ("\x00null",)
    if isinstance(v, float):
        if math.isnan(v):
            return ("nan",)
        if approx:
            return ("f", f"{v:.6e}")  # compare 7 significant digits
        return ("f", v)
    return v


def _canon_row(row, approx):
    return tuple(_canon(v, approx) for v in row)


def _sort_key(row):
    return tuple((v is None, str(type(v)), str(v)) for v in row)


def rows_of(obj):
    import pyarrow as pa
    if isinstance(obj, pa.Table):
        cols = [obj.column(i).to_pylist() for i in range(obj.num_columns)]
        return list(zip(*cols)) if cols else []
    return list(obj)


def assert_rows_equal(actual, expected, ignore_order=True,
                      approx_float=True):
    a, e = rows_of(actual), rows_of(expected)
    assert len(a) == len(e), f"row count {len(a)} != {len(e)}\nactual={a[:10]}\nexpected={e[:10]}"
    ac = [_canon_row(r, approx_float) for r in a]
    ec = [_canon_row(r, approx_float) for r in e]
    if ignore_order:
        ac = sorted(ac, key=_sort_key)
        ec = sorted(ec, key=_sort_key)
    for i, (x, y) in enumerate(zip(ac, ec)):
        assert x == y, f"row {i}: {x} != {y}"


def assert_df_equals_pandas(df, pd_fn, ignore_order=True, approx_float=True):
    """Run our engine and a pandas reference over the same source."""
    actual = df.to_arrow()
    expected = pd_fn()
    assert_rows_equal(actual, expected, ignore_order, approx_float)


def indexed_rows(text, op):
    """Leading extent of the operand of every `op` (gather / scatter) in
    a lowered program's StableHLO."""
    import re
    return [int(m) for m in re.findall(
        r'"stablehlo\.%s"\(.*?\}[>)] : \(tensor<(\d+)[x>]' % op, text,
        flags=re.S)]
