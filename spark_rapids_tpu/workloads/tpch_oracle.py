"""Pandas oracles for all 22 TPC-H queries.

Independent implementations of the official query set used to verify the
engine's results (tests/test_tpch.py, chip_smoke.py). Written directly from the TPC-H v3 SQL — NOT by
translating tpch_queries.py — so an engine bug and an oracle bug would
have to coincide to go unseen.

Decimal columns arrive as float64 (converted by :func:`to_pandas`);
monetary sums therefore compare within rtol, counts exactly.
"""
from __future__ import annotations

import numpy as np
import pandas as pd

from .tpch import day


def to_pandas(tables: dict) -> dict:
    """pyarrow tables -> pandas frames with decimals as float64."""
    import pyarrow as pa
    out = {}
    for name, at in tables.items():
        df = pd.DataFrame()
        for c in at.column_names:
            colv = at.column(c)
            if pa.types.is_decimal(colv.type):
                df[c] = np.asarray(colv.cast(pa.float64()))
            else:
                df[c] = colv.to_pandas()
        out[name] = df
    return out


def _rev(li):
    return li["l_extendedprice"] * (1 - li["l_discount"])


def q1(t):
    li = t["lineitem"]
    m = li[li["l_shipdate"] <= 10471].copy()
    m["disc_price"] = _rev(m)
    m["charge"] = m["disc_price"] * (1 + m["l_tax"])
    g = m.groupby(["l_returnflag", "l_linestatus"], as_index=False).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"))
    return g.sort_values(["l_returnflag", "l_linestatus"])


def q2(t, size=15, type_suffix="BRASS", region="EUROPE"):
    n = t["nation"].merge(t["region"], left_on="n_regionkey",
                          right_on="r_regionkey")
    n = n[n["r_name"] == region]
    s = t["supplier"].merge(n, left_on="s_nationkey",
                            right_on="n_nationkey")
    ps = t["partsupp"].merge(s, left_on="ps_suppkey",
                             right_on="s_suppkey")
    p = t["part"]
    p = p[(p["p_size"] == size) & p["p_type"].str.endswith(type_suffix)]
    j = p.merge(ps, left_on="p_partkey", right_on="ps_partkey")
    mc = (ps.groupby("ps_partkey")["ps_supplycost"].min()
          .rename("min_cost").reset_index())
    j = j.merge(mc, on="ps_partkey")
    j = j[j["ps_supplycost"] == j["min_cost"]]
    j = j[["s_acctbal", "s_name", "n_name", "p_partkey", "p_mfgr",
           "s_address", "s_phone", "s_comment"]]
    return j.sort_values(["s_acctbal", "n_name", "s_name", "p_partkey"],
                         ascending=[False, True, True, True]).head(100)


def q3(t, segment="BUILDING", d="1995-03-15"):
    dd = day(d)
    c = t["customer"]
    c = c[c["c_mktsegment"] == segment]
    o = t["orders"]
    o = o[o["o_orderdate"] < dd].merge(c, left_on="o_custkey",
                                       right_on="c_custkey")
    li = t["lineitem"]
    li = li[li["l_shipdate"] > dd].merge(
        o, left_on="l_orderkey", right_on="o_orderkey").copy()
    li["revenue"] = _rev(li)
    g = li.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                   as_index=False)["revenue"].sum()
    return g.sort_values(["revenue", "o_orderdate"],
                         ascending=[False, True]).head(10)


def q4(t, d0="1993-07-01", d1="1993-10-01"):
    o = t["orders"]
    o = o[(o["o_orderdate"] >= day(d0)) & (o["o_orderdate"] < day(d1))]
    li = t["lineitem"]
    late_orders = li[li["l_commitdate"] < li["l_receiptdate"]][
        "l_orderkey"].unique()
    o = o[o["o_orderkey"].isin(late_orders)]
    g = (o.groupby("o_orderpriority").size()
         .rename("order_count").reset_index())
    return g.sort_values("o_orderpriority")


def q5(t, region="ASIA", d0="1994-01-01", d1="1995-01-01"):
    o = t["orders"]
    o = o[(o["o_orderdate"] >= day(d0)) & (o["o_orderdate"] < day(d1))]
    j = (t["customer"].merge(o, left_on="c_custkey", right_on="o_custkey")
         .merge(t["lineitem"], left_on="o_orderkey",
                right_on="l_orderkey")
         .merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey"))
    j = j[j["c_nationkey"] == j["s_nationkey"]]
    j = (j.merge(t["nation"], left_on="c_nationkey",
                 right_on="n_nationkey")
         .merge(t["region"], left_on="n_regionkey",
                right_on="r_regionkey"))
    j = j[j["r_name"] == region].copy()
    j["revenue"] = _rev(j)
    g = j.groupby("n_name", as_index=False)["revenue"].sum()
    return g.sort_values("revenue", ascending=False)


def q6(t):
    li = t["lineitem"]
    m = li[(li["l_shipdate"] >= 8766) & (li["l_shipdate"] < 9131)
           & (li["l_discount"] >= 0.05 - 1e-9)
           & (li["l_discount"] <= 0.07 + 1e-9)
           & (li["l_quantity"] < 24)]
    return pd.DataFrame(
        {"revenue": [(m["l_extendedprice"] * m["l_discount"]).sum()]})


def q7(t, n1="FRANCE", n2="GERMANY"):
    li = t["lineitem"]
    li = li[(li["l_shipdate"] >= day("1995-01-01"))
            & (li["l_shipdate"] <= day("1996-12-31"))]
    j = (li.merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
         .merge(t["orders"], left_on="l_orderkey", right_on="o_orderkey")
         .merge(t["customer"], left_on="o_custkey", right_on="c_custkey")
         .merge(t["nation"].rename(columns={"n_name": "supp_nation"}),
                left_on="s_nationkey", right_on="n_nationkey")
         .merge(t["nation"].rename(
             columns={"n_name": "cust_nation",
                      "n_nationkey": "n2_nationkey",
                      "n_regionkey": "n2_regionkey"}),
             left_on="c_nationkey", right_on="n2_nationkey"))
    j = j[((j["supp_nation"] == n1) & (j["cust_nation"] == n2))
          | ((j["supp_nation"] == n2) & (j["cust_nation"] == n1))].copy()
    j["l_year"] = np.where(j["l_shipdate"] <= day("1995-12-31"),
                           1995, 1996)
    j["revenue"] = _rev(j)
    g = j.groupby(["supp_nation", "cust_nation", "l_year"],
                  as_index=False)["revenue"].sum()
    return g.sort_values(["supp_nation", "cust_nation", "l_year"])


def _o_year(dates):
    bins = [day(f"{y}-12-31") for y in range(1992, 1998)]
    return np.searchsorted(bins, dates) + 1992


def q8(t, nation="BRAZIL", region="AMERICA",
       ptype="ECONOMY ANODIZED STEEL"):
    p = t["part"]
    p = p[p["p_type"] == ptype]
    o = t["orders"]
    o = o[(o["o_orderdate"] >= day("1995-01-01"))
          & (o["o_orderdate"] <= day("1996-12-31"))]
    j = (p.merge(t["lineitem"], left_on="p_partkey", right_on="l_partkey")
         .merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
         .merge(o, left_on="l_orderkey", right_on="o_orderkey")
         .merge(t["customer"], left_on="o_custkey", right_on="c_custkey")
         .merge(t["nation"], left_on="c_nationkey",
                right_on="n_nationkey")
         .merge(t["region"], left_on="n_regionkey",
                right_on="r_regionkey"))
    j = j[j["r_name"] == region]
    j = j.merge(t["nation"].rename(
        columns={"n_name": "supp_nation", "n_nationkey": "sn_key",
                 "n_regionkey": "sn_rk"}),
        left_on="s_nationkey", right_on="sn_key").copy()
    j["o_year"] = np.where(j["o_orderdate"] <= day("1995-12-31"),
                           1995, 1996)
    j["volume"] = _rev(j)
    j["nat"] = np.where(j["supp_nation"] == nation, j["volume"], 0.0)
    g = j.groupby("o_year", as_index=False).agg(
        nat=("nat", "sum"), total=("volume", "sum"))
    g["mkt_share"] = g["nat"] / g["total"]
    return g[["o_year", "mkt_share"]].sort_values("o_year")


def q9(t, word="green"):
    p = t["part"]
    p = p[p["p_name"].str.contains(word, regex=False)]
    j = (p.merge(t["lineitem"], left_on="p_partkey", right_on="l_partkey")
         .merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
         .merge(t["partsupp"],
                left_on=["l_partkey", "l_suppkey"],
                right_on=["ps_partkey", "ps_suppkey"])
         .merge(t["orders"], left_on="l_orderkey", right_on="o_orderkey")
         .merge(t["nation"], left_on="s_nationkey",
                right_on="n_nationkey")).copy()
    j["o_year"] = _o_year(j["o_orderdate"].to_numpy())
    j["amount"] = _rev(j) - j["ps_supplycost"] * j["l_quantity"]
    g = j.groupby(["n_name", "o_year"], as_index=False)["amount"].sum()
    g = g.rename(columns={"amount": "sum_profit"})
    return g.sort_values(["n_name", "o_year"], ascending=[True, False])


def q10(t, d0="1993-10-01", d1="1994-01-01"):
    o = t["orders"]
    o = o[(o["o_orderdate"] >= day(d0)) & (o["o_orderdate"] < day(d1))]
    li = t["lineitem"]
    li = li[li["l_returnflag"] == "R"]
    j = (t["customer"].merge(o, left_on="c_custkey", right_on="o_custkey")
         .merge(li, left_on="o_orderkey", right_on="l_orderkey")
         .merge(t["nation"], left_on="c_nationkey",
                right_on="n_nationkey")).copy()
    j["revenue"] = _rev(j)
    g = j.groupby(["c_custkey", "c_name", "c_acctbal", "c_phone",
                   "n_name", "c_address"], as_index=False)["revenue"].sum()
    return g.sort_values(["revenue", "c_custkey"],
                         ascending=[False, True]).head(20)


def q11(t, nation="GERMANY", fraction=0.0001):
    j = (t["partsupp"]
         .merge(t["supplier"], left_on="ps_suppkey", right_on="s_suppkey")
         .merge(t["nation"], left_on="s_nationkey",
                right_on="n_nationkey"))
    j = j[j["n_name"] == nation].copy()
    j["value"] = j["ps_supplycost"] * j["ps_availqty"]
    g = (j.groupby("ps_partkey")["value"].sum()
         .rename("part_value").reset_index())
    g = g[g["part_value"] > j["value"].sum() * fraction]
    return g.sort_values(["part_value", "ps_partkey"],
                         ascending=[False, True])


def q12(t, m1="MAIL", m2="SHIP", d0="1994-01-01", d1="1995-01-01"):
    li = t["lineitem"]
    li = li[li["l_shipmode"].isin([m1, m2])
            & (li["l_commitdate"] < li["l_receiptdate"])
            & (li["l_shipdate"] < li["l_commitdate"])
            & (li["l_receiptdate"] >= day(d0))
            & (li["l_receiptdate"] < day(d1))]
    j = li.merge(t["orders"], left_on="l_orderkey",
                 right_on="o_orderkey").copy()
    hi = j["o_orderpriority"].isin(["1-URGENT", "2-HIGH"])
    j["high_line_count"] = hi.astype(np.int64)
    j["low_line_count"] = (~hi).astype(np.int64)
    g = j.groupby("l_shipmode", as_index=False)[
        ["high_line_count", "low_line_count"]].sum()
    return g.sort_values("l_shipmode")


def q13(t, w1="special", w2="requests"):
    o = t["orders"]
    o = o[~o["o_comment"].str.contains(f"{w1}.*{w2}", regex=True)]
    j = t["customer"][["c_custkey"]].merge(
        o[["o_custkey", "o_orderkey"]], left_on="c_custkey",
        right_on="o_custkey", how="left")
    cc = (j.groupby("c_custkey")["o_orderkey"].count()
          .rename("c_count").reset_index())
    g = (cc.groupby("c_count").size().rename("custdist").reset_index())
    return g.sort_values(["custdist", "c_count"], ascending=[False, False])


def q14(t, d0="1995-09-01", d1="1995-10-01"):
    li = t["lineitem"]
    li = li[(li["l_shipdate"] >= day(d0)) & (li["l_shipdate"] < day(d1))]
    j = li.merge(t["part"], left_on="l_partkey",
                 right_on="p_partkey").copy()
    j["rev"] = _rev(j)
    promo = j["p_type"].str.startswith("PROMO")
    num = j.loc[promo, "rev"].sum()
    return pd.DataFrame(
        {"promo_revenue": [100.0 * num / j["rev"].sum()]})


def q15(t, d0="1996-01-01", d1="1996-04-01"):
    li = t["lineitem"]
    li = li[(li["l_shipdate"] >= day(d0))
            & (li["l_shipdate"] < day(d1))].copy()
    li["r"] = _rev(li)
    rev = (li.groupby("l_suppkey")["r"].sum()
           .rename("total_revenue").reset_index())
    mx = rev["total_revenue"].max()
    j = rev[rev["total_revenue"] == mx].merge(
        t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
    j = j[["s_suppkey", "s_name", "s_address", "s_phone",
           "total_revenue"]]
    return j.sort_values("s_suppkey")


def q16(t, brand="Brand#45", tprefix="MEDIUM POLISHED",
        sizes=(49, 14, 23, 45, 19, 3, 36, 9)):
    bad = t["supplier"]
    bad = bad[bad["s_comment"].str.contains("Customer.*Complaints",
                                            regex=True)]["s_suppkey"]
    ps = t["partsupp"]
    ps = ps[~ps["ps_suppkey"].isin(bad)]
    p = t["part"]
    p = p[(p["p_brand"] != brand)
          & ~p["p_type"].str.startswith(tprefix)
          & p["p_size"].isin(sizes)]
    j = ps.merge(p, left_on="ps_partkey", right_on="p_partkey")
    g = (j.groupby(["p_brand", "p_type", "p_size"])["ps_suppkey"]
         .nunique().rename("supplier_cnt").reset_index())
    return g.sort_values(["supplier_cnt", "p_brand", "p_type", "p_size"],
                         ascending=[False, True, True, True])


def q17(t, brand="Brand#23", container="MED BOX"):
    li = t["lineitem"]
    avg_qty = (li.groupby("l_partkey")["l_quantity"].mean() * 0.2)
    p = t["part"]
    p = p[(p["p_brand"] == brand) & (p["p_container"] == container)]
    j = p.merge(li, left_on="p_partkey", right_on="l_partkey")
    thr = j["l_partkey"].map(avg_qty)
    total = j.loc[j["l_quantity"] < thr, "l_extendedprice"].sum()
    return pd.DataFrame({"avg_yearly": [total / 7.0]})


def q18(t, qty=300):
    li = t["lineitem"]
    sums = li.groupby("l_orderkey")["l_quantity"].sum()
    big = sums[sums > qty].index
    o = t["orders"]
    o = o[o["o_orderkey"].isin(big)]
    j = (o.merge(t["customer"], left_on="o_custkey", right_on="c_custkey")
         .merge(li[["l_orderkey", "l_quantity"]],
                left_on="o_orderkey", right_on="l_orderkey"))
    g = j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                   "o_totalprice"], as_index=False)["l_quantity"].sum()
    g = g.rename(columns={"l_quantity": "sum_qty"})
    return g.sort_values(["o_totalprice", "o_orderdate", "o_orderkey"],
                         ascending=[False, True, True]).head(100)


def q19(t):
    li = t["lineitem"]
    li = li[li["l_shipmode"].isin(["AIR", "REG AIR"])
            & (li["l_shipinstruct"] == "DELIVER IN PERSON")]
    j = li.merge(t["part"], left_on="l_partkey", right_on="p_partkey")

    def branch(brand, containers, qlo, qhi, szhi):
        return ((j["p_brand"] == brand)
                & j["p_container"].isin(containers)
                & (j["l_quantity"] >= qlo) & (j["l_quantity"] <= qhi)
                & (j["p_size"] >= 1) & (j["p_size"] <= szhi))

    m = (branch("Brand#12", ["SM CASE", "SM BOX", "SM PACK", "SM PKG"],
                1, 11, 5)
         | branch("Brand#23", ["MED BAG", "MED BOX", "MED PKG",
                               "MED PACK"], 10, 20, 10)
         | branch("Brand#34", ["LG CASE", "LG BOX", "LG PACK", "LG PKG"],
                  20, 30, 15))
    return pd.DataFrame({"revenue": [_rev(j[m]).sum()]})


def q20(t, word="forest", nation="CANADA", d0="1994-01-01",
        d1="1995-01-01"):
    p = t["part"]
    pk = p[p["p_name"].str.startswith(word)]["p_partkey"]
    li = t["lineitem"]
    li = li[(li["l_shipdate"] >= day(d0)) & (li["l_shipdate"] < day(d1))]
    hq = (li.groupby(["l_partkey", "l_suppkey"])["l_quantity"].sum()
          * 0.5).rename("half_qty").reset_index()
    ps = t["partsupp"]
    ps = ps[ps["ps_partkey"].isin(pk)]
    ps = ps.merge(hq, left_on=["ps_partkey", "ps_suppkey"],
                  right_on=["l_partkey", "l_suppkey"])
    ps = ps[ps["ps_availqty"] > ps["half_qty"]]
    s = t["supplier"]
    s = s[s["s_suppkey"].isin(ps["ps_suppkey"].unique())]
    s = s.merge(t["nation"], left_on="s_nationkey",
                right_on="n_nationkey")
    s = s[s["n_name"] == nation]
    return s[["s_name", "s_address"]].sort_values("s_name")


def q21(t, nation="SAUDI ARABIA"):
    li = t["lineitem"]
    late = li[li["l_receiptdate"] > li["l_commitdate"]]
    n_supp = li.groupby("l_orderkey")["l_suppkey"].nunique()
    n_late = late.groupby("l_orderkey")["l_suppkey"].nunique()
    o = t["orders"]
    fo = set(o[o["o_orderstatus"] == "F"]["o_orderkey"])
    j = late[late["l_orderkey"].isin(fo)].copy()
    j["n_supp"] = j["l_orderkey"].map(n_supp)
    j["n_late"] = j["l_orderkey"].map(n_late)
    j = j[(j["n_supp"] > 1) & (j["n_late"] == 1)]
    j = (j.merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
         .merge(t["nation"], left_on="s_nationkey",
                right_on="n_nationkey"))
    j = j[j["n_name"] == nation]
    g = j.groupby("s_name").size().rename("numwait").reset_index()
    return g.sort_values(["numwait", "s_name"],
                         ascending=[False, True]).head(100)


def q22(t, codes=("13", "31", "23", "29", "30", "18", "17")):
    c = t["customer"].copy()
    c["cntrycode"] = c["c_phone"].str[:2]
    c = c[c["cntrycode"].isin(codes)]
    avg_bal = c.loc[c["c_acctbal"] > 0, "c_acctbal"].mean()
    has_orders = set(t["orders"]["o_custkey"])
    c = c[~c["c_custkey"].isin(has_orders)
          & (c["c_acctbal"] > avg_bal)]
    g = c.groupby("cntrycode", as_index=False).agg(
        numcust=("c_acctbal", "size"), totacctbal=("c_acctbal", "sum"))
    return g.sort_values("cntrycode")


ORACLES = {i: fn for i, fn in enumerate(
    [q1, q2, q3, q4, q5, q6, q7, q8, q9, q10, q11, q12, q13, q14, q15,
     q16, q17, q18, q19, q20, q21, q22], start=1)}
