"""Mean host-clock seconds of the executor's join phase per query
(`report()["phase_seconds"]["join"]`), in ms."""


def read(run):
    m = run.mean(lambda r: r["phase_seconds"].get("join", 0.0))
    return None if m is None else 1e3 * m
