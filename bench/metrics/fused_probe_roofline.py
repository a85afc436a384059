"""Share of the HBM roofline that the transfer phase's fused probe
programs reach, in %.

`rows_probed` counts the (key, filter) pairs the transfer phase probed:
live rows entering each filter, subqueries included, slot-cache replays
left out. Their least bytes (`bench.bytes`) over the chip's HBM
bandwidth is the least time; the share is that over the device time of
the `_fused_pallas_count` / `_fused_pallas_gather` programs, which read
those key columns from HBM and also hash, gather filter blocks, run the
Pallas probe kernel, count and compact. It cannot pass 100%."""
from bench.bytes import fused_probe_least_bytes

PROGRAMS = ("jit__fused_pallas_count", "jit__fused_pallas_gather")


def read(run):
    if run.trace is None:
        return None
    secs = run.trace.op_seconds(lambda n: n.startswith(PROGRAMS),
                                line="XLA Modules")
    pairs = sum(q.rows_probed for q in run.done)
    if secs <= 0 or pairs <= 0:
        return None
    least = fused_probe_least_bytes(pairs) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / secs
