"""PARTSUPP (clause 4.2.3): four rows a part, in part-key order; the i-th
supplier of part p is (p + i * (S/4 + (p-1)/S)) mod S + 1 with S suppliers,
the formula L_SUPPKEY draws one of (datagen/lineitem.py), so every
(l_partkey, l_suppkey) of LINEITEM is exactly one row here. PS_SUPPLYCOST
uniform on 1.00..1000.00, PS_AVAILQTY on 1..9999."""
import numpy as np
import pyarrow as pa

from benchmarks.datagen import common as c

SUPPLIERS_A_PART = 4


def generate(sf, seed, made):
    parts, s = c.rows("part", sf), c.rows("supplier", sf)
    n = SUPPLIERS_A_PART * parts      # no row count of its own in common
    rng = c.stream(seed, 8)
    partkey = np.repeat(np.arange(1, parts + 1, dtype=np.int64),
                        SUPPLIERS_A_PART)
    i = np.tile(np.arange(SUPPLIERS_A_PART, dtype=np.int64), parts)
    return pa.table({
        "ps_partkey": pa.array(partkey),
        "ps_suppkey": pa.array(
            (partkey + i * (s // 4 + (partkey - 1) // s)) % s + 1),
        "ps_availqty": pa.array(rng.integers(1, 10_000, n, dtype=np.int32)),
        "ps_supplycost": c.decimal_from_unscaled(
            rng.integers(100, 100_001, n), 12, 2),
        "ps_comment": c.text(rng, seed, n, 49, 198),
    })
