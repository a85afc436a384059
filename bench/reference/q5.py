"""Q5 in plain numpy: revenue of orders of one year whose customer and
supplier share a nation of one region, by nation."""
import numpy as np

from bench.reference import epoch_day, group_sum, lookup, where


def answer(ref, p):
    t = ref.t
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    s, n, r = t["supplier"], t["nation"], t["region"]
    lo = epoch_day(f"{p['year']}-01-01")
    hi = epoch_day(f"{p['year'] + 1}-01-01")
    regions = r["r_regionkey"][where(r["r_name"], lambda v: v == p["region"])]
    nations = n["n_nationkey"][np.isin(n["n_regionkey"], regions)]
    oidx = np.flatnonzero((o["o_orderdate"] >= lo) & (o["o_orderdate"] < hi))
    cnat = c["c_nationkey"][lookup(c["c_custkey"], o["o_custkey"][oidx])]
    orow = lookup(o["o_orderkey"][oidx], li["l_orderkey"])
    lidx = np.flatnonzero(orow >= 0)
    cnat = cnat[orow[lidx]]
    snat = s["s_nationkey"][lookup(s["s_suppkey"], li["l_suppkey"][lidx])]
    keep = (snat == cnat) & np.isin(snat, nations)
    lidx, snat = lidx[keep], snat[keep]
    rev = (ref.money("lineitem", "l_extendedprice")[lidx]
           * (1 - ref.money("lineitem", "l_discount")[lidx]))
    names = n["n_name"].decode()[lookup(n["n_nationkey"], snat)]
    keys, inv = np.unique(names, return_inverse=True)
    revenue = group_sum(inv, rev, len(keys), ref.dtype)
    order = sorted(range(len(keys)), key=lambda i: -float(revenue[i]))
    return {"n_name": keys[order], "revenue": revenue[order]}
