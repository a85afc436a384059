"""Mean host time per query blocked on device->host syncs
(`report()["spans"]["device.wait"]`: every `device_plane.to_host` and
`scalar` of a device value), in ms."""
from bench.spans import span_ms


def read(run):
    return span_ms(run, "device.wait")
