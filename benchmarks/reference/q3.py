"""TPC-H Q3 (clause 2.4.3, validation parameters: BUILDING, 1995-03-15).
revenue = sum(l_extendedprice * (1 - l_discount)): decimal(18,4) summed to
(28,4); ORDER BY revenue DESC, o_orderdate; the first 10 rows. o_orderkey is
the orders' primary key, so a group of (l_orderkey, o_orderdate,
o_shippriority) is a group of l_orderkey."""
import numpy as np

from benchmarks.reference.common import Answer, codes, f32, to_unscaled, unscaled

NAMES = ["l_orderkey", "o_orderdate", "o_shippriority", "revenue"]
CUT = 9204  # 1995-03-15 in days since 1970-01-01


def _joined(t):
    """(order key, order date, ship priority, price, discount) of every
    lineitem row that survives both joins and all three filters."""
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    seg, seg_names = codes(c["c_mktsegment"])
    building = c["c_custkey"].to_numpy()[
        seg == seg_names.index("BUILDING")] if "BUILDING" in seg_names else []
    okey, odate = o["o_orderkey"].to_numpy(), o["o_orderdate"].to_numpy()
    if len(np.unique(okey)) != len(okey):
        raise ValueError("o_orderkey is not unique")
    keep_o = (odate < CUT) & np.isin(o["o_custkey"].to_numpy(), building)
    order = np.argsort(okey[keep_o])
    okey_s = okey[keep_o][order]
    lkey = li["l_orderkey"].to_numpy()
    pos = np.searchsorted(okey_s, lkey)
    pos[pos == len(okey_s)] = 0
    keep_l = (li["l_shipdate"].to_numpy() > CUT) & (len(okey_s) > 0)
    if len(okey_s):
        keep_l &= okey_s[pos] == lkey
    at = pos[keep_l]
    return (lkey[keep_l], odate[keep_o][order][at],
            o["o_shippriority"].to_numpy()[keep_o][order][at],
            unscaled(li["l_extendedprice"])[keep_l],
            unscaled(li["l_discount"])[keep_l])


def _top10(key, date, prio, revenue, as_int):
    """Group by order key, then ORDER BY revenue DESC, o_orderdate LIMIT 10:
    the first 10 and every further group that ties with the 10th."""
    if not len(key):
        return []
    by = np.argsort(key, kind="stable")
    starts = np.flatnonzero(np.r_[True, key[by][1:] != key[by][:-1]])
    total = np.add.reduceat(revenue[by], starts)
    first = by[starts]
    top = np.lexsort((date[first], -total))
    if len(top) > 10:
        last = top[9]
        tied = ((total[top] == total[last])
                & (date[first][top] == date[first][last]))
        top = top[(np.arange(len(top)) < 10) | tied]
    return [(int(key[first][i]), int(date[first][i]), int(prio[first][i]),
             as_int(total[i])) for i in top]


def _answer(rows):
    return Answer(NAMES, [None, None, None, (28, 4)], rows,
                  order_by=[(3, False), (1, True)], limit=10)


def reference(t):
    key, date, prio, price, disc = _joined(t)
    # at most 7 lines an order times 1.05e9: no int64 overflow at any scale
    return _answer(_top10(key, date, prio, price * (100 - disc), int))


def control(t):
    key, date, prio, price, disc = _joined(t)
    rev = f32(price, 2) * (np.float32(1) - f32(disc, 2))
    return _answer(_top10(key, date, prio, rev, lambda v: to_unscaled(v, 4)))
