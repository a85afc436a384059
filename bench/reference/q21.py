"""Q21 in plain numpy: suppliers of one nation who alone were late on a
finished multi-supplier order."""
import numpy as np

from bench.reference import lookup, pair_key, where


def _suppliers_per_order(orderkey, suppkey, rows):
    """For each row of `rows`: distinct suppliers of its order among
    `rows`."""
    span = int(suppkey.max(initial=0)) + 1
    pairs = np.unique(pair_key(orderkey[rows], suppkey[rows], span))
    orders, count = np.unique(pairs // span, return_counts=True)
    return count[np.searchsorted(orders, orderkey[rows])]


def _waiting_lines(ref):
    """Rows of lineitem that are late, on an F order, with another
    supplier on the order and no other late supplier."""
    li, o = ref.t["lineitem"], ref.t["orders"]
    ok, sk = li["l_orderkey"], li["l_suppkey"]
    every = np.arange(len(ok))
    late = np.flatnonzero(li["l_receiptdate"] > li["l_commitdate"])
    multi = _suppliers_per_order(ok, sk, every)[late] >= 2
    alone = _suppliers_per_order(ok, sk, late) == 1
    done = o["o_orderkey"][where(o["o_orderstatus"], lambda v: v == "F")]
    finished = lookup(done, ok[late]) >= 0
    return late[multi & alone & finished]


def answer(ref, p):
    li, s, n = ref.t["lineitem"], ref.t["supplier"], ref.t["nation"]
    rows = ref.memo("q21.waiting_lines", lambda: _waiting_lines(ref))
    nation = n["n_nationkey"][where(n["n_name"], lambda v: v == p["nation"])]
    srow = lookup(s["s_suppkey"], li["l_suppkey"][rows])
    srow = srow[np.isin(s["s_nationkey"][srow], nation)]
    names, count = np.unique(s["s_name"].decode()[srow], return_counts=True)
    order = sorted(range(len(names)), key=lambda i: (-count[i], names[i]))
    order = order[:100]
    return {"s_name": names[order], "numwait": count[order]}
