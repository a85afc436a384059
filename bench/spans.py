"""The program's host spans in a profiler trace, set against the
device's idle time.

`repro.core.device_plane.span(name)` writes a `pt.<name>` annotation on
the thread that runs it: the served query's phases (`pt.scan`,
`pt.transfer`, `pt.join`), the layer boundaries inside them, and every
host<->device crossing (`pt.device.wait`, `pt.device.upload`). In a
`--trace 1` run they land in the trace beside the device's op events, on
the same clock. This module reads them from the trace file and answers:

* how long the device sat idle inside each phase (`idle_in`): the idle
  time inside the union of the phase's outermost spans, a phase span
  nested in another phase span (a subquery's) counted with the outer;
* what the host was doing in each idle gap (`idle_gaps`):
  `<template>.<outermost phase>/<innermost open span>`, e.g.
  `q21.scan/join.aggregate`; in the worker's `pt.serve.execute` but
  outside the program's `pt.query`, `<template>.serve`; in the client's
  query but outside `pt.serve.execute` (the queue, the hand-off to the
  worker and back), `<template>.handoff`;
* how many syncs JAX made outside a `pt.device.wait` span
  (`syncs_outside_wait`): device->host copies the program's counters
  miss.

The readers assume one worker thread, as the cells serve with
(`workers` 1): with two, one thread's scan overlapping another's
transfer would count the idle time they share once for each phase, and
a gap would be named by the first thread with a span open. Outside
`pt.device.wait` only the syncs JAX marks `np.asarray(jax.Array)` are
counted: `int()`, `float()` and `bool()` of a jax.Array, and on the TPU
`np.asarray` of one; `.item()` and a `block_until_ready` wait leave no
such event (on the CPU `np.asarray` leaves none either).

The spans' in-memory record (`report()["spans"]`, `perf_counter_ns`) is
read by `span_ms`; what is set against device time comes from the trace
alone.

A program that writes no `pt.*` spans gives nothing to read (`of`
returns None). Run on a trace directory, the module prints all of it:

    python3 -m bench.spans [.bench_trace]
"""
from __future__ import annotations

import glob
import json
import os
import sys
from typing import List, Optional, Sequence, Tuple

from bench.run import TRACE_DIR
from bench.trace import TOP, Interval, clip, union

PREFIX = "pt."
PHASES = ("scan", "transfer", "join")
QUERY = "query"
SERVE = "serve.execute"
WAIT = "device.wait"
# JAX's own host event for a device->host copy of a jax.Array
SYNC = "np.asarray(jax.Array)"

Span = Tuple[str, float, float]         # (name without "pt.", start, end)


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two sorted disjoint interval
    lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def open_at(spans: Sequence[Span], t: float) -> List[Span]:
    """The spans of one thread open at `t`, outermost first."""
    return sorted((s for s in spans if s[1] <= t < s[2]),
                  key=lambda s: (s[1], -s[2]))


class HostSpans:
    """`threads`: per host thread, its `pt.*` spans (name without the
    prefix, start ns, end ns), sorted by start; `syncs`: per thread, the
    (start, end) of JAX's `np.asarray(jax.Array)` events."""

    def __init__(self, threads: List[List[Span]],
                 syncs: List[List[Interval]]):
        self.threads = [sorted(t, key=lambda s: (s[1], -s[2]))
                        for t in threads]
        self.syncs = syncs

    @classmethod
    def load(cls, trace_dir: str) -> Optional["HostSpans"]:
        """The `pt.*` spans of the trace under `trace_dir`; None where
        there is no trace or it holds no `pt.*` span."""
        import jax
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            return None
        pd = jax.profiler.ProfileData.from_file(files[0])
        threads, syncs = [], []
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                spans, copies = [], []
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append((e.name[len(PREFIX):], e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name == SYNC:
                        copies.append((e.start_ns,
                                       e.start_ns + e.duration_ns))
                if spans or copies:
                    threads.append(spans)
                    syncs.append(copies)
        if not any(threads):
            return None
        return cls(threads, syncs)

    def phase_intervals(self, phase: str) -> List[Interval]:
        """Union of the outermost spans of `phase`: those inside no other
        phase span on their thread."""
        out = []
        for spans in self.threads:
            end = None          # end of the open outermost phase span
            for name, s, e in spans:
                if name not in PHASES:
                    continue
                if end is not None and s < end:
                    continue
                end = e
                if name == phase:
                    out.append((s, e))
        return union(out)

    def count(self, lo: float, hi: float) -> int:
        """Spans that start inside [lo, hi)."""
        return sum(lo <= s < hi for spans in self.threads
                   for _, s, _ in spans)

    def syncs_outside_wait(self, lo: float, hi: float) -> int:
        """JAX's device->host copies inside [lo, hi) that no
        `pt.device.wait` span on their thread holds."""
        n = 0
        for spans, copies in zip(self.threads, self.syncs):
            waits = union([(s, e) for name, s, e in spans if name == WAIT])
            for s, e in copies:
                if lo <= s < hi and overlap([(s, e)], waits) < e - s:
                    n += 1
        return n

    def name_at(self, trace, t: float) -> str:
        """What the host was doing at `t`: `client` between the client's
        queries; in a query, `<template>.handoff` outside the worker's
        `serve.execute` span, `<template>.serve` in it but outside the
        program's `query` span, else `<template>.<outermost
        phase>/<innermost open span>`."""
        tmpl = next((q for q, s, e in trace.queries if s <= t < e), None)
        if tmpl is None:
            return "client"
        serving = False
        for spans in self.threads:
            stack = open_at(spans, t)
            names = [name for name, _, _ in stack]
            if QUERY in names:
                phase = next((n for n in names if n in PHASES), QUERY)
                return f"{tmpl}.{phase}/{names[-1]}"
            serving = serving or SERVE in names
        return f"{tmpl}.serve" if serving else f"{tmpl}.handoff"


def span_ms(run, name: str) -> Optional[float]:
    """Mean in-memory seconds of span `name` per completed query
    (`report()["spans"]`), in ms; None where the reports carry no
    spans."""
    if not run.done or any("spans" not in q.report for q in run.done):
        return None
    return 1e3 * run.mean(lambda r: r["spans"].get(name, (0, 0.0))[1])


def of(run) -> Optional[HostSpans]:
    """The host spans of a traced run, read once from the trace file
    beside `run.trace`; None without a trace or without `pt.*` spans."""
    if run.trace is None:
        return None
    if not hasattr(run.trace, "host_spans"):
        run.trace.host_spans = HostSpans.load(TRACE_DIR)
    return run.trace.host_spans


def idle_in(trace, host: HostSpans, phase: Optional[str]) -> float:
    """Device idle seconds inside the window and inside the outermost
    spans of `phase`; `phase=None`: outside every phase. Averaged over
    the chips, as `Trace.busy_s` is."""
    if phase is None:
        inside = union([iv for p in PHASES
                        for iv in host.phase_intervals(p)])
        spans = _complement(clip(inside, trace.t0, trace.t1),
                            trace.t0, trace.t1)
    else:
        spans = clip(host.phase_intervals(phase), trace.t0, trace.t1)
    length = sum(e - s for s, e in spans)
    idle = [length - overlap(spans, trace.busy_intervals(c))
            for c in trace.ops]
    return sum(idle) / len(idle) / 1e9


def _complement(intervals: Sequence[Interval], lo: float, hi: float
                ) -> List[Interval]:
    edges = [lo] + [x for iv in intervals for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_gaps(trace, host: HostSpans, top: int = TOP) -> list:
    """The `top` longest idle gaps of the first chip inside the window,
    [[name, seconds]], named by `HostSpans.name_at` at their middle."""
    chip = min(trace.ops)
    gaps = _complement(trace.busy_intervals(chip), trace.t0, trace.t1)
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[host.name_at(trace, (s + e) / 2), (e - s) / 1e9]
            for s, e in gaps[:top]]


def summary(trace, host: HostSpans) -> dict:
    """Everything this module reads from one trace."""
    n = len(trace.queries)
    idle = {p: idle_in(trace, host, p) for p in PHASES}
    idle["outside"] = idle_in(trace, host, None)
    idle["window"] = trace.window_s - trace.busy_s
    return {"queries": n, "idle_s": idle,
            "spans_per_query": host.count(trace.t0, trace.t1) / max(n, 1),
            "syncs_outside_wait": host.syncs_outside_wait(trace.t0,
                                                          trace.t1),
            "idle_gaps": idle_gaps(trace, host)}


def main(argv: Sequence[str]) -> int:
    from bench.trace import Trace
    trace_dir = argv[0] if argv else TRACE_DIR
    host = HostSpans.load(trace_dir)
    if host is None:
        print(f"no {PREFIX}* spans under {trace_dir}", file=sys.stderr)
        return 1
    print(json.dumps(summary(Trace.load(trace_dir), host)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
