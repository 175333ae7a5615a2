"""SUPPLIER (clause 4.2.3): S_NATIONKEY uniform on 0..24, S_ACCTBAL on
-999.99..9999.99, S_PHONE's country code from the nation."""
import numpy as np
import pyarrow as pa

from benchmarks.datagen import common as c


def generate(sf, seed, made):
    n = c.rows("supplier", sf)
    rng = c.stream(seed, 7)
    key = np.arange(1, n + 1, dtype=np.int64)
    nation = rng.integers(0, 25, n)
    phone = np.char.add(np.char.add(np.char.add(
        (10 + nation).astype(str), "-"),
        np.char.add(rng.integers(100, 1000, n).astype(str), "-")),
        np.char.add(np.char.add(rng.integers(100, 1000, n).astype(str), "-"),
                    rng.integers(1000, 10000, n).astype(str)))
    return pa.table({
        "s_suppkey": pa.array(key),
        "s_name": c.numbered("Supplier#", key),
        "s_address": c.v_string(rng, n, 10, 40),
        "s_nationkey": pa.array(nation.astype(np.int64)),
        "s_phone": pa.array(phone, pa.string()),
        "s_acctbal": c.decimal_from_unscaled(
            rng.integers(-99_999, 1_000_000, n), 12, 2),
        "s_comment": c.text(rng, seed, n, 25, 100),
    })
