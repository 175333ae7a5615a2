"""TPC-H Q9, "Product Type Profit Measure" (clause 2.4.9, validation parameter
COLOR = green, 2.4.9.3). Six tables, five inner equi-joins, one of them on
two keys; GROUP BY n_name, year(o_orderdate); ORDER BY n_name, o_year DESC.

The type of sum_profit, by Spark's rules (DecimalPrecision): with
l_extendedprice, l_quantity and ps_supplycost decimal(12,2) and l_discount
decimal(4,2),
  1 - l_discount                       decimal(1,0) - (4,2)  = decimal(5,2)
  l_extendedprice * (1 - l_discount)   p1 + p2 + 1, s1 + s2  = decimal(18,4)
  ps_supplycost * l_quantity           12 + 12 + 1, 2 + 2    = decimal(25,4)
  amount, their difference    max(14, 21) + 4 + 1, max(4, 4) = decimal(26,4)
  sum(amount)                          p + 10, s             = decimal(36,4)
so an amount is the unscaled integer at scale 4,
price_cents * (100 - disc_cents) - cost_cents * qty_cents, and no step rounds.
o_year is Spark's year(): an integer, not a decimal."""
import numpy as np
import pyarrow.compute as pc

from benchmarks.reference.common import Answer, exact_sum, f32, to_unscaled, unscaled

NAMES = ["n_name", "o_year", "sum_profit"]
COLOUR = "green"


def _unique_sorted(keys, name):
    """(the keys in order, the row each came from); the key is a primary
    key, so it holds no value twice."""
    by = np.argsort(keys, kind="stable")
    ordered = keys[by]
    if len(ordered) > 1 and (ordered[1:] == ordered[:-1]).any():
        raise ValueError(f"{name} is not unique")
    return ordered, by


def _lookup(keys, name, wanted):
    """An inner equi-join against a primary key by sorted lookup: (the row
    of `keys` each wanted value is found in, whether it is found at all)."""
    ordered, by = _unique_sorted(keys, name)
    if not len(ordered):
        return np.zeros(len(wanted), np.int64), np.zeros(len(wanted), bool)
    at = np.minimum(np.searchsorted(ordered, wanted), len(ordered) - 1)
    return by[at], ordered[at] == wanted


def year_of(days):
    """The calendar year of int32 days since 1970-01-01."""
    return (days.astype("datetime64[D]").astype("datetime64[Y]")
            .astype(np.int64) + 1970)


def _joined(t, colour):
    """(nation key, order year, price, discount, supply cost, quantity) of
    every lineitem row that survives the filter on p_name and all five
    joins; the four decimals as unscaled int64."""
    p, s, li = t["part"], t["supplier"], t["lineitem"]
    ps, o = t["partsupp"], t["orders"]
    green = p["p_partkey"].to_numpy()[
        pc.match_substring(p["p_name"], colour).to_numpy(
            zero_copy_only=False)]
    lpart, lsupp = li["l_partkey"].to_numpy(), li["l_suppkey"].to_numpy()
    _, keep = _lookup(green, "p_partkey", lpart)                 # part
    rows = np.flatnonzero(keep)
    s_row, has = _lookup(s["s_suppkey"].to_numpy(), "s_suppkey",
                         lsupp[rows])                            # supplier
    rows, s_row = rows[has], s_row[has]
    # partsupp, on both keys: one integer a pair, which keeps their order
    width = int(max(ps["ps_suppkey"].to_numpy().max(initial=0),
                    lsupp.max(initial=0))) + 1
    ps_row, has = _lookup(
        ps["ps_partkey"].to_numpy() * width + ps["ps_suppkey"].to_numpy(),
        "(ps_partkey, ps_suppkey)", lpart[rows] * width + lsupp[rows])
    rows, s_row, ps_row = rows[has], s_row[has], ps_row[has]
    o_row, has = _lookup(o["o_orderkey"].to_numpy(), "o_orderkey",
                         li["l_orderkey"].to_numpy()[rows])      # orders
    rows, s_row, ps_row, o_row = rows[has], s_row[has], ps_row[has], o_row[has]
    nation = s["s_nationkey"].to_numpy()[s_row]
    _, has = _lookup(t["nation"]["n_nationkey"].to_numpy(), "n_nationkey",
                     nation)                                     # nation
    rows, ps_row, o_row, nation = rows[has], ps_row[has], o_row[has], nation[has]
    return (nation, year_of(o["o_orderdate"].to_numpy()[o_row]),
            unscaled(li["l_extendedprice"])[rows],
            unscaled(li["l_discount"])[rows],
            unscaled(ps["ps_supplycost"])[ps_row],
            unscaled(li["l_quantity"])[rows])


def joined_rows(t, colour=COLOUR):
    """How many rows reach the amount: what the program's 128-bit
    expressions evaluate (tests/, harness/d128bytes.py count it alike)."""
    return len(_joined(t, colour)[0])


def _grouped(t, nation, year, amount, total):
    """One row a (n_name, o_year), ORDER BY n_name, o_year DESC."""
    n = t["nation"]
    name_of = dict(zip(n["n_nationkey"].to_pylist(), n["n_name"].to_pylist()))
    by = np.lexsort((year, nation))
    nation, year, amount = nation[by], year[by], amount[by]
    starts = np.flatnonzero(np.r_[True, (nation[1:] != nation[:-1])
                                  | (year[1:] != year[:-1])]) \
        if len(by) else np.zeros(0, np.int64)
    ends = np.r_[starts[1:], len(by)]
    rows = [(name_of[int(nation[a])], int(year[a]), total(amount[a:b]))
            for a, b in zip(starts, ends)]
    rows.sort(key=lambda r: (r[0], -r[1]))
    return Answer(NAMES, [None, None, (36, 4)], rows,
                  order_by=[(0, True), (1, False)])


def reference(t, colour=COLOUR):
    nation, year, price, disc, cost, qty = _joined(t, colour)
    # |amount| < 1.1e9 a row: int64 holds it, and the sums are Python's
    return _grouped(t, nation, year, price * (100 - disc) - cost * qty,
                    exact_sum)


def control(t, colour=COLOUR):
    """Decimals in float32: an amount of a few hundred thousand at scale 4
    is past float32's 24 bits, and a group's sum of about 1,900 of them
    (SF1) far past: sum_profit comes back off the grid's right value in
    every group."""
    nation, year, price, disc, cost, qty = _joined(t, colour)
    amount = (f32(price, 2) * (np.float32(1) - f32(disc, 2))
              - f32(cost, 2) * f32(qty, 2))
    return _grouped(t, nation, year, amount,
                    lambda v: to_unscaled(v.sum(dtype=np.float32), 4))
