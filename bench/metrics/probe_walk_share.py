"""Share of the fused Bloom probes' widths that they walked over the
window, in %: `probe_rows_walked` over `probe_width`
(`report()["device"]`), summed over the window's queries. A probe walks
its live prefix in tiles, so the share falls as the live rows leave
more of the power-of-two bucket empty. None where the reports have no
such counters, or no probe ran."""


def read(run):
    devs = [q.report["device"] for q in run.done]
    if any("probe_width" not in d for d in devs):
        return None
    width = sum(d["probe_width"] for d in devs)
    if width <= 0:
        return None
    return 100.0 * sum(d["probe_rows_walked"] for d in devs) / width
