"""Device time of the join phase's segment-join programs per query of
the window, in ms: the jitted `_segjoin_*` graphs of
`repro.kernels.semijoin.ops` (match counts, output offsets, emit)."""
PREFIX = "jit__segjoin_"


def read(run):
    if run.trace is None or not run.done:
        return None
    secs = run.trace.op_seconds(lambda n: n.startswith(PREFIX),
                                line="XLA Modules")
    return 1e3 * secs / len(run.done) if secs > 0 else None
