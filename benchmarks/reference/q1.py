"""TPC-H Q1 (clause 2.4.1, DELTA 90: l_shipdate <= 1998-09-02), with Spark's
decimal result types: sums of decimal(12,2) are decimal(22,2); disc_price =
price * (1 - disc) is decimal(18,4), summed to (28,4); charge = disc_price *
(1 + tax) is decimal(24,6), summed to (34,6); avg of decimal(p,s) is
decimal(p+4,s+4), rounded HALF_UP."""
import numpy as np

from benchmarks.reference.common import (Answer, codes, exact_sum, f32,
                                         spark_avg, to_unscaled, unscaled)

NAMES = ["l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
         "sum_disc_price", "sum_charge", "avg_qty", "avg_price", "avg_disc",
         "count_order"]
DECIMALS = [None, None, (22, 2), (22, 2), (28, 4), (34, 6), (16, 6), (16, 6),
            (8, 6), None]
SHIP_MAX = 10471


def _groups(t):
    li = t["lineitem"]
    keep = li["l_shipdate"].to_numpy() <= SHIP_MAX
    rf, rf_names = codes(li["l_returnflag"])
    ls, ls_names = codes(li["l_linestatus"])
    group = (rf * len(ls_names) + ls)[keep]
    cols = [unscaled(li[c])[keep] for c in
            ("l_quantity", "l_extendedprice", "l_discount", "l_tax")]
    for g in np.unique(group):
        idx = np.flatnonzero(group == g)
        yield (rf_names[g // len(ls_names)], ls_names[g % len(ls_names)],
               [c[idx] for c in cols])


def reference(t):
    rows = []
    for flag, status, (qty, price, disc, tax) in _groups(t):
        n = len(qty)
        disc_price = price * (100 - disc)
        s_qty, s_price, s_disc = (exact_sum(x) for x in (qty, price, disc))
        rows.append((flag, status, s_qty, s_price, exact_sum(disc_price),
                     exact_sum(disc_price * (100 + tax)),
                     spark_avg(s_qty, n, 12, 2), spark_avg(s_price, n, 12, 2),
                     spark_avg(s_disc, n, 4, 2), n))
    return Answer(NAMES, DECIMALS, rows)


def control(t):
    rows = []
    for flag, status, cols in _groups(t):
        qty, price, disc, tax = (f32(c, 2) for c in cols)
        disc_price = price * (np.float32(1) - disc)
        charge = disc_price * (np.float32(1) + tax)
        vals = [qty.sum(), price.sum(), disc_price.sum(), charge.sum(),
                qty.mean(), price.mean(), disc.mean()]
        rows.append((flag, status,
                     *(to_unscaled(v, d[1]) for v, d in zip(vals, DECIMALS[2:9])),
                     len(qty)))
    return Answer(NAMES, DECIMALS, rows)
