"""Reduction of a JAX profiler trace to device busy time, kernel times
and idle gaps.

The trace is the `.xplane.pb` file that `jax.profiler.start_trace`
writes. Read with `jax.profiler.ProfileData`:

* each chip is a plane `/device:TPU:<n>`; its line `XLA Ops` holds one
  event per operation the chip ran (fusions, copies, Pallas custom
  calls), and its line `XLA Modules` one event per jitted program run;
* host threads are planes `/host:...`; the harness's annotations are
  events there: `bench.window` around the measured window and
  `query.<template>` around each query.

Busy time is the union of the op intervals inside the window, averaged
over the chips the cell uses; idle time is the rest of the window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
QUERY = "query."
TOP = 10

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint cover of `intervals`."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class Trace:
    """Device events of the measured window: `ops[chip]` and
    `modules[chip]` are lists of (name, start_ns, end_ns); `queries` the
    host's per-query spans (template, start_ns, end_ns); `t0`, `t1` the
    window's edges."""

    def __init__(self, ops: Dict[int, list], modules: Dict[int, list],
                 queries: list, t0: float, t1: float, phases=None):
        self.ops, self.modules, self.queries = ops, modules, queries
        self.t0, self.t1 = t0, t1
        # per query: [(phase, seconds)] in order, from its report
        self.phases = phases or [[] for _ in queries]

    @classmethod
    def load(cls, trace_dir: str, run=None, chips: int = 1) -> "Trace":
        import jax
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise ValueError(f"{len(files)} trace files under {trace_dir}")
        pd = jax.profiler.ProfileData.from_file(files[0])
        ops: Dict[int, list] = {}
        modules: Dict[int, list] = {}
        window: Optional[Interval] = None
        queries = []
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m is not None and line.name in (OPS_LINE, MODULES_LINE):
                    dest = ops if line.name == OPS_LINE else modules
                    dest.setdefault(int(m.group(1)), []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
                elif m is None:
                    for e in line.events:
                        if e.name == WINDOW:
                            window = (e.start_ns, e.start_ns + e.duration_ns)
                        elif e.name.startswith(QUERY):
                            queries.append((e.name[len(QUERY):], e.start_ns,
                                            e.start_ns + e.duration_ns))
        if window is None:
            raise ValueError(f"no {WINDOW!r} span in the trace")
        if not ops:
            raise ValueError("no device op events in the trace")
        queries.sort(key=lambda q: q[1])
        phases = None
        if run is not None and len(run.queries) == len(queries):
            phases = [list((q.report or {}).get("phase_seconds", {}).items())
                      for q in run.queries]
        keep = sorted(ops)[:chips]
        return cls({c: ops[c] for c in keep},
                   {c: modules.get(c, []) for c in keep},
                   queries, window[0], window[1], phases)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def busy_intervals(self, chip: int) -> List[Interval]:
        return union(clip([(s, e) for _, s, e in self.ops[chip]],
                          self.t0, self.t1))

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        per = [sum(e - s for s, e in self.busy_intervals(c))
               for c in self.ops]
        return sum(per) / len(per) / 1e9

    def op_seconds(self, match, line: str = OPS_LINE) -> float:
        """Device seconds of the events whose name `match(name)` accepts,
        inside the window, summed over the chips."""
        src = self.ops if line == OPS_LINE else self.modules
        total = 0.0
        for events in src.values():
            total += sum(e - s for s, e in clip(
                [(s, e) for n, s, e in events if match(n)],
                self.t0, self.t1))
        return total / 1e9

    def what_host_did(self, t: float) -> str:
        """The query (and, from its report, the phase) running at time
        `t`; `client` between queries."""
        for i, (tmpl, s, e) in enumerate(self.queries):
            if s <= t < e:
                at = (t - s) / 1e9
                for phase, secs in self.phases[i]:
                    if at < secs:
                        return f"{tmpl}.{phase}"
                    at -= secs
                return f"{tmpl}.serve"
        return "client"

    def program_of(self, chip: int):
        """A function mapping a device time to the name of the program
        (XLA module, its fingerprint dropped) running then on `chip`."""
        mods = sorted((s, e, n.split("(")[0])
                      for n, s, e in self.modules.get(chip, []))
        starts = [m[0] for m in mods]

        def at(t: float) -> str:
            i = bisect.bisect_right(starts, t) - 1
            return mods[i][2] if i >= 0 and t < mods[i][1] else "?"
        return at

    def breakdown(self) -> dict:
        """The device ops that took the most time (self time: nested
        ops are counted once, in the innermost), named `<program>/<op>`
        (`jit__fused_pallas_count/%fusion`), and the longest idle gaps
        named by what the host was doing then."""
        per_op: Dict[str, float] = {}
        for chip, events in self.ops.items():
            program = self.program_of(chip)
            for n, s, secs in self_times(clip_named(events, self.t0,
                                                    self.t1)):
                op = n.split(" = ")[0].split(".")[0]
                key = f"{program(s)}/{op}"
                per_op[key] = per_op.get(key, 0.0) + secs
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
        chip = min(self.ops)
        busy = self.busy_intervals(chip)
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        idle = [[self.what_host_did((s + e) / 2), (e - s) / 1e9]
                for s, e in gaps[:TOP]]
        return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": idle}


def self_times(events):
    """(name, start, self seconds) of each event: its duration less that
    of the events nested in it (a `while` op holds its body's fusions)."""
    events = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    child = [0.0] * len(events)
    stack: List[int] = []
    for i, (_, s, e) in enumerate(events):
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
        stack.append(i)
    return [(n, s, (e - s - c) / 1e9)
            for (n, s, e), c in zip(events, child)]


def clip_named(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]
