"""ORDERS (clause 4.2.3): o_custkey never a multiple of 3, o_orderstatus and
o_totalprice reckoned from the order's lines, o_shippriority 0."""
import numpy as np
import pyarrow as pa

from benchmarks.datagen import common as c
from benchmarks.datagen import lineitem


def generate(sf, seed, made):
    v = made.get("_lineitem_numbers") or lineitem.numbers(sf, seed)
    okey, odate, _ = lineitem.order_core(sf, seed)
    n = len(okey)
    rng = c.stream(seed, 4)
    customers = c.rows("customer", sf)
    j = rng.integers(0, max(customers - customers // 3, 1), n)
    # dbgen's integer cents: price less discount, then with tax
    charged = (v["l_extendedprice"] * (100 - v["l_discount"]) // 100
               * (100 + v["l_tax"]) // 100)
    open_lines = np.add.reduceat(v["l_linestatus"].astype(np.int64),
                                 v["_first"])
    lines = np.diff(np.r_[v["_first"], len(v["l_orderkey"])])
    status = np.where(open_lines == 0, 0, np.where(open_lines == lines, 1, 2))
    priority = rng.integers(0, 5, n)
    clerk = rng.integers(1, max(int(1000 * sf), 1) + 1, n)
    texts = c.parallel([lambda: c.pick(c.PRIORITIES, priority),
                        lambda: c.numbered("Clerk#", clerk),
                        lambda: c.text(rng, seed, n, 19, 78)])
    return pa.table({
        "o_orderkey": pa.array(okey),
        "o_custkey": pa.array(3 * (j // 2) + 1 + j % 2),
        "o_orderstatus": c.flag("FOP", status),
        "o_totalprice": c.decimal_from_unscaled(
            np.add.reduceat(charged, v["_first"]), 15, 2),
        "o_orderdate": pa.array(odate),
        "o_orderpriority": texts[0],
        "o_clerk": texts[1],
        "o_shippriority": pa.array(np.zeros(n, np.int32)),
        "o_comment": texts[2],
    })
