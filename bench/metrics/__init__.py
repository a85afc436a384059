"""One reader per metric, `<metric>.py`, found by the metric's name in
`BENCHMARK.json`. Each exposes `read(run: bench.record.Run)` and returns
the metric's value, or None where the run has nothing to read."""
