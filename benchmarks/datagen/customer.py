"""CUSTOMER (clause 4.2.3)."""
import numpy as np
import pyarrow as pa

from benchmarks.datagen import common as c


def generate(sf, seed, made):
    n = c.rows("customer", sf)
    rng = c.stream(seed, 5)
    key = np.arange(1, n + 1, dtype=np.int64)
    nation = rng.integers(0, 25, n)
    phone = np.char.add(np.char.add(np.char.add(
        (10 + nation).astype(str), "-"),
        np.char.add(rng.integers(100, 1000, n).astype(str), "-")),
        np.char.add(np.char.add(rng.integers(100, 1000, n).astype(str), "-"),
                    rng.integers(1000, 10000, n).astype(str)))
    return pa.table({
        "c_custkey": pa.array(key),
        "c_name": c.numbered("Customer#", key),
        "c_address": c.v_string(rng, n, 10, 40),
        "c_nationkey": pa.array(nation.astype(np.int64)),
        "c_phone": pa.array(phone, pa.string()),
        "c_acctbal": c.decimal_from_unscaled(
            rng.integers(-99_999, 1_000_000, n), 12, 2),
        "c_mktsegment": c.pick(c.SEGMENTS, rng.integers(0, 5, n)),
        "c_comment": c.text(rng, seed, n, 29, 116),
    })
