"""Device time of the transfer phase's fused probe programs per query of
the window, in ms: the jitted `_fused_pallas_count` /
`_fused_pallas_gather` graphs — key gather, hashing, filter block-row
gather, the Pallas probe kernel, live counts and survivor compaction."""
PROGRAMS = ("jit__fused_pallas_count", "jit__fused_pallas_gather")


def read(run):
    if run.trace is None or not run.done:
        return None
    secs = run.trace.op_seconds(lambda n: n.startswith(PROGRAMS),
                                line="XLA Modules")
    return 1e3 * secs / len(run.done) if secs > 0 else None
