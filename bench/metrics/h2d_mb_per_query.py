"""Host-to-device bytes uploaded per query (`report()["device"]
["h2d_bytes"]`), mean, in MB (10^6 bytes)."""


def read(run):
    m = run.mean(lambda r: r["device"]["h2d_bytes"])
    return None if m is None else m / 1e6
