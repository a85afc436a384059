"""Mean time a query waited in `QueryServer`'s admission queue, from
submit to worker pickup (`report()["spans"]["serve.queued"]`), in ms."""
from bench.spans import span_ms


def read(run):
    return span_ms(run, "serve.queued")
