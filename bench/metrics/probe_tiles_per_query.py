"""Tiles the transfer phase's fused Bloom probes walked per query of the
window (`report()["device"]["probe_tiles"]`), mean: each probe walks its
live prefix in tiles, up to the tile that holds its last live row. None
where the reports have no such counter."""


def read(run):
    devs = [q.report["device"] for q in run.done]
    if not devs or any("probe_tiles" not in d for d in devs):
        return None
    return sum(d["probe_tiles"] for d in devs) / len(devs)
