"""Host<->device traffic accounting and host spans for one query run.

The device-resident refactor (DESIGN.md section 15) keeps transfer and
join intermediates on the accelerator; the host only schedules.  Its
claim — "fewer host<->device round trips" — must be measurable, so the
boundary is crossed only through the functions here.  A query run wraps
itself in :func:`track`; with no active context every counter is a
no-op, so library code can call them unconditionally.

Crossings, each counted and timed as it happens:

``to_device``  host -> device upload (filter words, key halves,
               validity); the only sanctioned h2d path.  Runs inside a
               ``device.upload`` span.
``to_host``    device -> host array sync, counted at its nbytes.
``scalar``     device -> host scalar sync (``int(x.sum())``), counted
               as ``SCALAR_BYTES``.
               These two are the only sanctioned d2h paths: each runs
               inside a ``device.wait`` span, so the sync is counted
               where the host blocks on the device, and a bare
               ``np.asarray`` of a device array is a fault.  The *sync
               count* (not bytes) is what the round-trip gate watches.

:func:`span` names a stretch of host work: it is a
``jax.profiler.TraceAnnotation`` ``pt.<name>`` (under a profiler session
it lands in the trace on the running thread, on the clock of the
device's op events) and, inside :func:`track`, a (count, nanoseconds)
entry of ``DeviceStats.spans`` read with ``time.perf_counter_ns``.
Spans name query phases and layer boundaries, never per-row or
per-block work.

The record is thread-local: concurrent queries through ``repro.serve``
each see only their own traffic.  Nested contexts attribute to the
innermost one; the executor merges subquery stats upward explicitly
(mirroring how ``ExecStats.subqueries`` works).
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

SCALAR_BYTES = 8


@dataclass
class DeviceStats:
    """Host<->device boundary-crossing counts for one query run."""

    h2d_syncs: int = 0
    h2d_bytes: int = 0
    d2h_syncs: int = 0
    d2h_bytes: int = 0
    fused_calls: int = 0          # fused multi-filter probe invocations
    # survivor compactions after a fused probe: how many, the slots they
    # searched (the survivors' buckets) and the probe widths a
    # compaction over the whole probe would have searched
    compact_calls: int = 0
    compact_slots: int = 0
    compact_width: int = 0
    # fused probes' walks over their live prefix: the tiles walked, the
    # rows in them, and the probes' full widths
    probe_tiles: int = 0
    probe_rows_walked: int = 0
    probe_width: int = 0
    # segment joins, and those whose build keys share one high half, so
    # that their search compares one key plane
    segjoin_calls: int = 0
    segjoin_narrow: int = 0
    # span name -> [count, nanoseconds] (`span`; `serve.queued` is set
    # by the server)
    spans: Dict[str, List[int]] = field(default_factory=dict)

    def add_span(self, name: str, ns: int, count: int = 1) -> None:
        rec = self.spans.get(name)
        if rec is None:
            self.spans[name] = [count, ns]
        else:
            rec[0] += count
            rec[1] += ns

    def round_trips(self) -> int:
        return self.h2d_syncs + self.d2h_syncs

    def merge(self, other: "DeviceStats") -> None:
        self.h2d_syncs += other.h2d_syncs
        self.h2d_bytes += other.h2d_bytes
        self.d2h_syncs += other.d2h_syncs
        self.d2h_bytes += other.d2h_bytes
        self.fused_calls += other.fused_calls
        self.compact_calls += other.compact_calls
        self.compact_slots += other.compact_slots
        self.compact_width += other.compact_width
        self.probe_tiles += other.probe_tiles
        self.probe_rows_walked += other.probe_rows_walked
        self.probe_width += other.probe_width
        self.segjoin_calls += other.segjoin_calls
        self.segjoin_narrow += other.segjoin_narrow
        for name, (count, ns) in other.spans.items():
            self.add_span(name, ns, count)

    def report(self) -> dict:
        return {
            "h2d_syncs": self.h2d_syncs,
            "h2d_bytes": self.h2d_bytes,
            "d2h_syncs": self.d2h_syncs,
            "d2h_bytes": self.d2h_bytes,
            "round_trips": self.round_trips(),
            "fused_calls": self.fused_calls,
            "compact_calls": self.compact_calls,
            "compact_slots": self.compact_slots,
            "compact_width": self.compact_width,
            "probe_tiles": self.probe_tiles,
            "probe_rows_walked": self.probe_rows_walked,
            "probe_width": self.probe_width,
            "segjoin_calls": self.segjoin_calls,
            "segjoin_narrow": self.segjoin_narrow,
        }

    def span_report(self) -> dict:
        """{name: [count, seconds]}."""
        return {name: [count, ns / 1e9]
                for name, (count, ns) in sorted(self.spans.items())}


_tls = threading.local()


def active() -> DeviceStats | None:
    return getattr(_tls, "stats", None)


@contextmanager
def track(stats: DeviceStats):
    """Attribute boundary crossings on this thread to ``stats``."""
    prev = getattr(_tls, "stats", None)
    _tls.stats = stats
    try:
        yield stats
    finally:
        _tls.stats = prev


class span:
    """``with span("scan"):`` — a ``pt.scan`` profiler annotation, and
    inside :func:`track` one count and the elapsed nanoseconds added to
    the active record's ``spans["scan"]``; ``.seconds`` is this span's
    own time (0.0 outside :func:`track`)."""

    __slots__ = ("name", "ns", "_ann", "_stats", "_t0")

    def __init__(self, name: str):
        self.name = name
        self.ns = 0
        self._ann = TraceAnnotation("pt." + name)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._stats = active()
        if self._stats is not None:
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        if self._stats is not None:
            self.ns = time.perf_counter_ns() - self._t0
            self._stats.add_span(self.name, self.ns)
        self._ann.__exit__(*exc)

    @property
    def seconds(self) -> float:
        return self.ns / 1e9


def _count_h2d(nbytes: int) -> None:
    s = active()
    if s is not None:
        s.h2d_syncs += 1
        s.h2d_bytes += int(nbytes)


def _count_d2h(nbytes: int) -> None:
    s = active()
    if s is not None:
        s.d2h_syncs += 1
        s.d2h_bytes += int(nbytes)


def count_fused() -> None:
    s = active()
    if s is not None:
        s.fused_calls += 1


def count_compact(size: int, width: int) -> None:
    s = active()
    if s is not None:
        s.compact_calls += 1
        s.compact_slots += int(size)
        s.compact_width += int(width)


def count_probe_walk(tiles: int, tile: int, width: int) -> None:
    s = active()
    if s is not None:
        s.probe_tiles += int(tiles)
        s.probe_rows_walked += int(tiles) * int(tile)
        s.probe_width += int(width)


def count_segjoin(narrow: bool) -> None:
    s = active()
    if s is not None:
        s.segjoin_calls += 1
        s.segjoin_narrow += int(narrow)


def scalar(x) -> int:
    """``int(x)`` for a device scalar: one d2h sync, waited for inside
    a ``device.wait`` span."""
    with span("device.wait"):
        _count_d2h(SCALAR_BYTES)
        return int(x)


def to_host(a):
    """``np.asarray``; a device array is one d2h sync, waited for inside
    a ``device.wait`` span (host arrays are free)."""
    if isinstance(a, np.ndarray) or not hasattr(a, "__array__"):
        return np.asarray(a)
    with span("device.wait"):
        out = np.asarray(a)
    _count_d2h(out.nbytes)
    return out


def to_device(a, sharding=None):
    """``jnp.asarray`` (``jax.device_put`` onto `sharding` when given);
    a host array is one h2d upload inside a ``device.upload`` span
    (device arrays are free)."""
    if not isinstance(a, np.ndarray):
        return _put(a, sharding)
    with span("device.upload"):
        _count_h2d(a.nbytes)
        return _put(a, sharding)


def _put(a, sharding):
    return jnp.asarray(a) if sharding is None \
        else jax.device_put(a, sharding)
