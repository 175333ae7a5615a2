"""The comparison that decides `correct`: an Arrow table that the engine
returned against a reference `Answer`, exactly (limit 0 mismatches)."""
import collections
import decimal

import pyarrow as pa


def _unscaled(value, scale):
    """A Decimal's unscaled integer, with no context's rounding in the way."""
    sign, digits, exponent = value.as_tuple()
    if exponent != -scale:
        raise ValueError(f"{value}: exponent {exponent}, scale {scale}")
    n = int("".join(map(str, digits)))
    return -n if sign else n


def to_table(answer):
    """An `Answer` as the Arrow table an engine would return for it: how the
    control is put in the program's place. Of rows that tie across a LIMIT
    it returns the first."""
    rows = answer.rows[:answer.limit]
    cols = {}
    for i, (name, typ) in enumerate(zip(answer.names, answer.decimals)):
        vals = [r[i] for r in rows]
        if typ is not None:
            vals = pa.array([None if v is None else
                             decimal.Decimal(v).scaleb(-typ[1]) for v in vals],
                            pa.decimal128(*typ))
        cols[name] = vals
    return pa.table(cols)


def rows_of(table, answer):
    """The engine's rows in the reference's terms; raises ValueError where
    names, a decimal's precision or scale, or a column's kind differ from
    Spark's."""
    if table.column_names != answer.names:
        raise ValueError(f"columns {table.column_names} != {answer.names}")
    cols = []
    for name, typ in zip(answer.names, answer.decimals):
        got = table.schema.field(name).type
        vals = table.column(name).to_pylist()
        if typ is None:
            if pa.types.is_decimal(got) or pa.types.is_floating(got):
                raise ValueError(f"{name}: {got} where no decimal is due")
        else:
            if (not pa.types.is_decimal(got)
                    or (got.precision, got.scale) != tuple(typ)):
                raise ValueError(f"{name}: {got}, Spark's is decimal{typ}")
            vals = [None if v is None else _unscaled(v, typ[1]) for v in vals]
        cols.append(vals)
    return list(zip(*cols))


def _sort_key(row):
    return tuple((v is None, v) for v in row)


def mismatches(table, answer):
    """How many rows of the engine's table differ from the reference's: 0
    where it is right. An unordered result is compared as a multiset. An
    ordered one has to run in ORDER BY order and hold the same rows; rows
    whose ORDER BY keys tie may come in any order, and where they tie across
    the LIMIT any of the tied rows may fill the last places."""
    try:
        got = rows_of(table, answer)
    except ValueError:
        return max(table.num_rows, len(answer.rows), 1)
    want, tied = list(answer.rows), collections.Counter()
    if answer.order_by:
        def key(row):
            return tuple((row[i] is None,
                          0 if row[i] is None else row[i] if asc else -row[i])
                         for i, asc in answer.order_by)
        keys = [key(r) for r in got]
        bad = sum(a > b for a, b in zip(keys, keys[1:]))
        got = sorted(got, key=lambda r: (key(r), _sort_key(r)))
        want = sorted(want, key=lambda r: (key(r), _sort_key(r)))
        if answer.limit and len(want) > answer.limit:
            last = key(want[answer.limit - 1])
            tied = collections.Counter(r for r in want if key(r) == last)
            want = [r for r in want if key(r) < last]
            for row in got[len(want):answer.limit]:   # any of the tied rows
                bad += tied[row] <= 0
                tied[row] -= 1
            bad += abs(len(got) - answer.limit)
            got = got[:len(want)]
    else:
        bad = 0
        got, want = sorted(got, key=_sort_key), sorted(want, key=_sort_key)
    return (bad + abs(len(got) - len(want))
            + sum(g != w for g, w in zip(got, want)))
