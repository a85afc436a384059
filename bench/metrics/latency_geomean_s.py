"""Geometric mean of the client latency of every query in the window
(TPC-H's power-test weighting: a short query counts as much as Q9)."""
import math


def read(run):
    lat = [q.latency_s for q in run.done]
    return math.exp(sum(math.log(x) for x in lat) / len(lat)) if lat else None
