"""Mean host-clock seconds of the executor's scan phase per query
(`report()["phase_seconds"]["scan"]`), in ms."""


def read(run):
    m = run.mean(lambda r: r["phase_seconds"].get("scan", 0.0))
    return None if m is None else 1e3 * m
