"""Share of the window's queries whose scan and transfer the server
replayed from its slot cache (`report()["transfer"]["from_cache"]`),
in %."""


def read(run):
    done = [q for q in run.done if q.report["transfer"] is not None]
    if not done:
        return None
    hits = sum(bool(q.report["transfer"]["from_cache"]) for q in done)
    return 100.0 * hits / len(done)
