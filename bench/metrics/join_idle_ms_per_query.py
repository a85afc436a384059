"""Device idle time inside the program's outermost `pt.join` spans,
per query of the window, in ms (`bench.spans.idle_in`)."""
from bench import spans


def read(run):
    host = spans.of(run)
    if host is None or not run.done:
        return None
    return 1e3 * spans.idle_in(run.trace, host, "join") / len(run.done)
