"""LINEITEM and the part of ORDERS it hangs on (clause 4.2.3): sparse order
keys (the first 8 of every 32), 1 to 7 lines an order, ship, commit and
receipt dates reckoned from the order's date, return flag and line status
from those dates and CURRENTDATE, the extended price from the part's retail
price. Rows come in order-key order, as dbgen writes them.

One departure, so that every seed gives the same row count (a run's work
must not hang on the seed): the lines-per-order draws are uniform on 1..7
and then a few thousand orders move by one line, so that the table has the
specification's 6,001,215 x SF rows exactly."""
import numpy as np
import pyarrow as pa

from benchmarks.datagen import common as c


def order_core(sf, seed):
    """(o_orderkey, o_orderdate, lines per order) of every order."""
    n = c.rows("orders", sf)
    rng = c.stream(seed, 1)
    i = np.arange(n, dtype=np.int64)
    okey = (i // 8) * 32 + i % 8 + 1
    odate = rng.integers(c.STARTDATE, c.ENDDATE - 151 + 1, n, dtype=np.int32)
    lines = rng.integers(1, 8, n, dtype=np.int8)
    off = (min(max(c.rows("lineitem", sf), n), 7 * n)
           - int(lines.sum(dtype=np.int64)))
    room = np.flatnonzero(lines < 7 if off > 0 else lines > 1)
    lines[rng.permutation(room)[:abs(off)]] += np.sign(off)
    return okey, odate, lines


def retail_price(partkey):
    """p_retailprice in cents (clause 4.2.3, PART)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def numbers(sf, seed):
    """The numeric columns, as numpy arrays by column name; each group of
    columns from a stream and on a thread of its own."""
    okey, odate, lines = order_core(sf, seed)
    n = int(lines.sum(dtype=np.int64))
    first = np.cumsum(lines, dtype=np.int64) - lines
    of_order = np.repeat(np.arange(len(lines), dtype=np.int32), lines)

    def keys():
        return {"l_orderkey": okey[of_order],
                "l_linenumber": (np.arange(n, dtype=np.int64)
                                 - first[of_order] + 1).astype(np.int32)}

    def parts():
        rng = c.stream(seed, 20)
        partkey = rng.integers(1, c.rows("part", sf) + 1, n, dtype=np.int64)
        s = c.rows("supplier", sf)
        quantity = rng.integers(1, 51, n, dtype=np.int64)
        return {"l_partkey": partkey,
                "l_suppkey": (partkey + rng.integers(0, 4, n)
                              * (s // 4 + (partkey - 1) // s)) % s + 1,
                "l_quantity": quantity * 100,
                "l_extendedprice": quantity * retail_price(partkey)}

    def rates():
        rng = c.stream(seed, 21)
        return {"l_discount": rng.integers(0, 11, n, dtype=np.int64),
                "l_tax": rng.integers(0, 9, n, dtype=np.int64)}

    def shipped():
        rng = c.stream(seed, 22)
        shipdate = odate[of_order] + rng.integers(1, 122, n, dtype=np.int32)
        receipt = shipdate + rng.integers(1, 31, n, dtype=np.int32)
        return {
            "l_shipdate": shipdate, "l_receiptdate": receipt,
            # R or A where the line was received by CURRENTDATE, else N
            "l_returnflag": np.where(
                receipt <= c.CURRENTDATE,
                rng.integers(0, 2, n, dtype=np.int8) * 2, 1),
            "l_linestatus": (shipdate > c.CURRENTDATE).astype(np.int8)}

    def committed():
        return {"l_commitdate": odate[of_order] + c.stream(seed, 23).integers(
            30, 91, n, dtype=np.int32)}

    v = {"_first": first}
    for part in c.parallel([keys, parts, rates, shipped, committed]):
        v.update(part)
    return v


def generate(sf, seed, made):
    v = made.get("_lineitem_numbers") or numbers(sf, seed)
    n = len(v["l_orderkey"])
    plain = ["l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
             "l_shipdate", "l_commitdate", "l_receiptdate"]
    made_of = {
        "l_quantity": lambda: c.decimal_from_unscaled(v["l_quantity"], 12, 2),
        "l_extendedprice": lambda: c.decimal_from_unscaled(
            v["l_extendedprice"], 12, 2),
        "l_discount": lambda: c.decimal_from_unscaled(v["l_discount"], 4, 2),
        "l_tax": lambda: c.decimal_from_unscaled(v["l_tax"], 4, 2),
        "l_returnflag": lambda: c.flag("ANR", v["l_returnflag"]),
        "l_linestatus": lambda: c.flag("FO", v["l_linestatus"]),
        "l_shipinstruct": lambda: c.pick(
            c.INSTRUCTIONS, c.stream(seed, 30).integers(0, 4, n)),
        "l_shipmode": lambda: c.pick(
            c.MODES, c.stream(seed, 31).integers(0, 7, n)),
        "l_comment": lambda: c.text(c.stream(seed, 32), seed, n, 10, 43),
    }
    columns = dict(zip(made_of, c.parallel(made_of.values())))
    columns.update({name: pa.array(v[name]) for name in plain})
    return pa.table({name: columns[name] for name in (
        "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
        "l_extendedprice", "l_discount", "l_tax", "l_returnflag",
        "l_linestatus", "l_shipdate", "l_commitdate", "l_receiptdate",
        "l_shipinstruct", "l_shipmode", "l_comment")})
