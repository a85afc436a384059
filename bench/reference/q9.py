"""Q9 in plain numpy: profit on parts of one colour, by supplier nation
and order year."""
import numpy as np

from bench.reference import group_sum, join, lookup, pair_key, where


def _order_years(o):
    days = o["o_orderdate"].astype("datetime64[D]")
    return days.astype("datetime64[Y]").astype(np.int64) + 1970


def answer(ref, p):
    t = ref.t
    part, li, s = t["part"], t["lineitem"], t["supplier"]
    ps, o, n = t["partsupp"], t["orders"], t["nation"]
    parts = part["p_partkey"][where(part["p_name"],
                                    lambda v: p["color"] in v)]
    lidx = np.flatnonzero(np.isin(li["l_partkey"], parts))
    span = int(max(ps["ps_suppkey"].max(), li["l_suppkey"].max())) + 1
    pick, psrow = join(pair_key(ps["ps_partkey"], ps["ps_suppkey"], span),
                       pair_key(li["l_partkey"][lidx], li["l_suppkey"][lidx],
                                span))
    lidx = lidx[pick]
    snat = s["s_nationkey"][lookup(s["s_suppkey"], li["l_suppkey"][lidx])]
    years = ref.memo("q9.order_years", lambda: _order_years(o))
    year = years[lookup(o["o_orderkey"], li["l_orderkey"][lidx])]
    amount = (ref.money("lineitem", "l_extendedprice")[lidx]
              * (1 - ref.money("lineitem", "l_discount")[lidx])
              - ref.money("partsupp", "ps_supplycost")[psrow]
              * ref.money("lineitem", "l_quantity")[lidx])
    nrow = lookup(n["n_nationkey"], snat)
    keys, inv = np.unique(nrow * 10000 + year, return_inverse=True)
    profit = group_sum(inv, amount, len(keys), ref.dtype)
    names = n["n_name"].decode()[keys // 10000]
    gyear = keys % 10000
    order = sorted(range(len(keys)), key=lambda i: (names[i], -gyear[i]))
    return {"nation": names[order], "o_year": gyear[order],
            "sum_profit": profit[order]}
