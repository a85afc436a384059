"""Device time of the Pallas Bloom build kernel (`build_pallas` custom
calls) per query of the window, in ms."""
KERNEL = "%build_pallas"


def read(run):
    if run.trace is None or not run.done:
        return None
    secs = run.trace.op_seconds(lambda n: n.startswith(KERNEL)
                                and "custom-call" in n)
    return 1e3 * secs / len(run.done) if secs > 0 else None
