"""TPC-H Q6 (clause 2.4.6, validation parameters): 1994, discount 0.06 +-
0.01, quantity < 24. revenue = sum(l_extendedprice * l_discount):
decimal(12,2) * decimal(4,2) = decimal(17,4), summed to decimal(27,4)."""
from benchmarks.reference.common import Answer, exact_sum, f32, to_unscaled, unscaled


def _columns(t):
    li = t["lineitem"]
    return (li["l_shipdate"].to_numpy(), unscaled(li["l_discount"]),
            unscaled(li["l_quantity"]), unscaled(li["l_extendedprice"]))


def reference(t):
    ship, disc, qty, price = _columns(t)
    m = ((ship >= 8766) & (ship < 9131) & (disc >= 5) & (disc <= 7)
         & (qty < 2400))
    rev = exact_sum(price[m] * disc[m]) if m.any() else None
    return Answer(["revenue"], [(27, 4)], [(rev,)])


def control(t):
    ship, disc, qty, price = _columns(t)
    d, q, p = f32(disc, 2), f32(qty, 2), f32(price, 2)
    m = ((ship >= 8766) & (ship < 9131) & (d >= 0.05) & (d <= 0.07)
         & (q < 24))
    rev = to_unscaled((p[m] * d[m]).sum(), 4) if m.any() else None
    return Answer(["revenue"], [(27, 4)], [(rev,)])
