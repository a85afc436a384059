"""Process start to window start: imports, catalog generation, server
start and warm-up (compilation, or loading it from the cache)."""


def read(run):
    return run.setup_s
