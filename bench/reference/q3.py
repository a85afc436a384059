"""Q3 in plain numpy: the 10 unshipped orders of highest revenue."""
import numpy as np

from bench.reference import epoch_day, group_sum, lookup, where


def answer(ref, p):
    c, o, li = ref.t["customer"], ref.t["orders"], ref.t["lineitem"]
    cutoff = epoch_day(p["date"])
    custs = c["c_custkey"][where(c["c_mktsegment"],
                                 lambda s: s == p["segment"])]
    oidx = np.flatnonzero((o["o_orderdate"] < cutoff)
                          & (lookup(custs, o["o_custkey"]) >= 0))
    lidx = np.flatnonzero(li["l_shipdate"] > cutoff)
    orow = lookup(o["o_orderkey"][oidx], li["l_orderkey"][lidx])
    lidx, oi = lidx[orow >= 0], oidx[orow[orow >= 0]]
    rev = (ref.money("lineitem", "l_extendedprice")[lidx]
           * (1 - ref.money("lineitem", "l_discount")[lidx]))
    keys, first, inv = np.unique(li["l_orderkey"][lidx], return_index=True,
                                 return_inverse=True)
    revenue = group_sum(inv, rev, len(keys), ref.dtype)
    odate = o["o_orderdate"][oi][first]
    oship = o["o_shippriority"][oi][first]
    top = np.lexsort((odate, -revenue.astype(np.float64)))[:10]
    return {"l_orderkey": keys[top], "o_orderdate": odate[top],
            "o_shippriority": oship[top], "revenue": revenue[top]}
