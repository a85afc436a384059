"""95th percentile of the client latency over all queries of the
window; a failed query counts as slower than every answered one."""
import math


def read(run):
    lat = sorted(q.latency_s if q.report is not None else math.inf
                 for q in run.queries)
    if not lat:
        return None
    # nearest rank: the smallest latency that 95% of queries meet
    v = lat[max(0, math.ceil(0.95 * len(lat)) - 1)]
    return v if math.isfinite(v) else None
