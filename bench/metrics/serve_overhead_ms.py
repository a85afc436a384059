"""Client latency minus the executor's own phase time, mean per query:
what `QueryServer` adds (queueing, hand-off, report)."""


def read(run):
    vals = [q.latency_s - q.report["total_seconds"] for q in run.done]
    return 1e3 * sum(vals) / len(vals) if vals else None
