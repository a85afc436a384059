"""Host<->device syncs per query (`report()["device"]["round_trips"]`),
mean."""


def read(run):
    return run.mean(lambda r: r["device"]["round_trips"])
