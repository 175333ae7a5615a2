"""What the plain references share: the answer's shape and exact helpers."""
import dataclasses

import numpy as np

from benchmarks.datagen.common import unscaled  # noqa: F401  (re-exported)


@dataclasses.dataclass
class Answer:
    """Rows as tuples; a decimal is its unscaled integer at the type Spark
    gives the column: `decimals[i]` is (precision, scale), None for a column
    that is no decimal. `order_by` is [(column index, ascending)] for an
    ORDER BY, else None. With a `limit`, `rows` holds the first `limit` rows
    and every further row that ties with the last of them on the ORDER BY
    keys: any of the tied rows may close a right answer."""
    names: list
    decimals: list
    rows: list
    order_by: list = None
    limit: int = None


def exact_sum(x):
    """Sum of an int64 array as a Python int: no overflow at any scale."""
    return sum(int(c.sum()) for c in np.array_split(x, len(x) // (1 << 20) + 1))


def div_half_up(a, b):
    """a / b rounded HALF_UP (away from zero on a tie), b > 0, exact."""
    q, r = divmod(abs(a), b)
    if 2 * r >= b:
        q += 1
    return q if a >= 0 else -q


def spark_avg(total, count, precision, scale):
    """Spark's avg of decimal(precision, scale), as the unscaled integer of
    its decimal(precision+4, scale+4) result. Spark divides the sum,
    decimal(precision+10, scale), by the count as decimal(20, 0): that
    quotient has scale max(6, scale+21), cut to fit 38 digits but to no less
    than 6, and is rounded HALF_UP there; the cast to the result's scale
    rounds HALF_UP once more."""
    sum_p = precision + 10
    q_scale = max(6, scale + 21)
    q_prec = sum_p - scale + q_scale
    if q_prec > 38:
        q_scale = max(38 - (q_prec - q_scale), min(q_scale, 6))
    q = div_half_up(total * 10 ** (q_scale - scale), count)
    return div_half_up(q, 10 ** (q_scale - (scale + 4)))


def codes(column):
    """(int codes, list of distinct values) of a string column."""
    d = column.combine_chunks().dictionary_encode()
    return d.indices.to_numpy(zero_copy_only=False), d.dictionary.to_pylist()


def f32(unscaled_values, scale):
    """The float32 a lower-precision engine would hold for a decimal."""
    return (unscaled_values.astype(np.float32)
            / np.float32(10 ** scale))


def to_unscaled(value, scale):
    """A float result back on the decimal grid (the control's last step)."""
    return int(round(float(value) * 10 ** scale))
