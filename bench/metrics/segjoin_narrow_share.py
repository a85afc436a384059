"""Share of the window's segment joins whose build keys share one high
half, so that their search compared one key plane, in %:
`segjoin_narrow` over `segjoin_calls` (`report()["device"]`), summed
over the window's queries. None where the reports have no such
counters, or no segment join ran."""


def read(run):
    devs = [q.report["device"] for q in run.done]
    if any("segjoin_calls" not in d for d in devs):
        return None
    calls = sum(d["segjoin_calls"] for d in devs)
    if calls <= 0:
        return None
    return 100.0 * sum(d["segjoin_narrow"] for d in devs) / calls
