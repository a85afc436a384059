"""Q17 in plain numpy: yearly revenue lost on small orders of one brand
and container."""
import numpy as np

from bench.reference import group_sum, where


def _mean_quantity(li):
    """Per part key: (sorted keys, mean l_quantity)."""
    keys, inv = np.unique(li["l_partkey"], return_inverse=True)
    total = np.bincount(inv, weights=li["l_quantity"].astype(np.float64))
    return keys, total / np.bincount(inv)


def answer(ref, p):
    part, li = ref.t["part"], ref.t["lineitem"]
    sel = (where(part["p_brand"], lambda v: v == p["brand"])
           & where(part["p_container"], lambda v: v == p["container"]))
    lidx = np.flatnonzero(np.isin(li["l_partkey"], part["p_partkey"][sel]))
    keys, mean = ref.memo("q17.mean_quantity", lambda: _mean_quantity(li))
    avg = mean[np.searchsorted(keys, li["l_partkey"][lidx])]
    lidx = lidx[li["l_quantity"][lidx] < 0.2 * avg]
    price = ref.money("lineitem", "l_extendedprice")[lidx]
    total = group_sum(np.zeros(len(lidx), np.int64), price, 1, ref.dtype)
    return {"avg_yearly": total / 7.0}
