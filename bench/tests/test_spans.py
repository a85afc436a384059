"""The program's host spans set against the device: per-phase idle time,
span-named idle gaps, the span readers, and spans in a trace recorded
on the CPU."""
import os
from types import SimpleNamespace

import pytest

from bench import spans
from bench.record import Query, Run
from bench.run import BENCH, load_module
from bench.spans import HostSpans, idle_gaps, idle_in
from bench.trace import Trace

NEW_METRICS = ("queue_wait_ms_per_query", "device_wait_ms_per_query",
               "scan_idle_ms_per_query", "transfer_idle_ms_per_query",
               "join_idle_ms_per_query")


def _metric(name, run):
    return load_module(os.path.join(BENCH, "metrics", name + ".py")).read(run)


def _synthetic():
    """A 1000 ns window holding two client queries, q3 [40, 600) and q5
    [600, 1000); the device busy [100, 150), [160, 300), [400, 500),
    [700, 800), so idle 610 ns. The worker's spans: q3's scan holds a
    subquery whose join aggregates across the idle gap [150, 160); its
    transfer waits on the device across the gap [300, 400)."""
    ops = {0: [("%fusion.1", 100, 150), ("%fusion.2", 160, 300),
               ("%fusion.3", 400, 500), ("%fusion.4", 700, 800)]}
    trace = Trace(ops, {0: []}, [("q3", 40, 600), ("q5", 600, 1000)],
                  0, 1000)
    worker = [
        ("serve.execute", 60, 590), ("query", 70, 580),
        ("scan", 70, 200), ("subquery", 80, 190), ("query", 85, 185),
        ("join", 90, 185), ("join.aggregate", 90, 185),
        ("transfer", 200, 450), ("device.upload", 210, 220),
        ("device.wait", 300, 360),
        ("join", 450, 580), ("join.materialize", 500, 560),
        ("serve.execute", 610, 990), ("query", 620, 980),
        ("scan", 620, 650), ("transfer", 650, 900), ("join", 900, 980)]
    syncs = [(305, 310), (455, 460)]    # the second outside device.wait
    trace.host_spans = HostSpans([worker], [syncs])
    return trace


def _run(trace, reports):
    return Run("sf1-adhoc", 1.0, 1e-6,
               [Query(f"q{i}", {}, 1e-6, r) for i, r in enumerate(reports)],
               0, {"hbm_bytes_per_s": 819e9}, trace)


def test_idle_per_phase():
    tr = _synthetic()
    host = tr.host_spans
    # the subquery's join [90, 185) is scan time: its outer phase
    assert host.phase_intervals("scan") == [(70, 200), (620, 650)]
    assert host.phase_intervals("join") == [(450, 580), (900, 980)]
    assert idle_in(tr, host, "scan") == pytest.approx(70e-9)
    assert idle_in(tr, host, "transfer") == pytest.approx(250e-9)
    assert idle_in(tr, host, "join") == pytest.approx(160e-9)
    assert idle_in(tr, host, None) == pytest.approx(130e-9)


def test_phase_idle_adds_up_to_the_window_idle():
    """Per-phase idle plus idle outside the phases is the window's idle
    time, (1 - busy/window) x window."""
    tr = _synthetic()
    total = sum(idle_in(tr, tr.host_spans, p)
                for p in spans.PHASES + (None,))
    assert total == pytest.approx((1 - tr.busy_s / tr.window_s)
                                  * tr.window_s)
    assert total == pytest.approx(610e-9)


def test_gaps_are_named_by_spans():
    tr = _synthetic()
    gaps = dict(idle_gaps(tr, tr.host_spans))
    # [0, 100) and [500, 700): their middles lie in the client's query
    # before the worker's serve.execute opens, in the hand-off
    assert gaps == {
        "q5.handoff": pytest.approx(200e-9),        # [500, 700)
        "q5.join/join": pytest.approx(200e-9),      # [800, 1000)
        "q3.handoff": pytest.approx(100e-9),        # [0, 100)
        "q3.transfer/device.wait": pytest.approx(100e-9),
        "q3.scan/join.aggregate": pytest.approx(10e-9)}
    name_at = tr.host_spans.name_at
    assert name_at(tr, 1200) == "client"
    assert name_at(tr, 20) == "client"
    # in serve.execute [60, 590) but outside the program's query [70, 580)
    assert name_at(tr, 65) == name_at(tr, 585) == "q3.serve"
    assert name_at(tr, 595) == "q3.handoff"
    assert name_at(tr, 615) == "q5.serve"
    assert name_at(tr, 995) == "q5.handoff"


def test_span_counts_and_syncs_outside_wait():
    tr = _synthetic()
    out = spans.summary(tr, tr.host_spans)
    assert out["spans_per_query"] == 17 / 2
    assert out["syncs_outside_wait"] == 1
    assert out["idle_s"]["window"] == pytest.approx(610e-9)


def test_new_readers():
    reports = [{"spans": {"serve.queued": [1, 0.002],
                          "device.wait": [3, 0.010]}},
               {"spans": {"serve.queued": [1, 0.004]}}]
    run = _run(_synthetic(), reports)
    assert _metric("queue_wait_ms_per_query", run) == pytest.approx(3.0)
    assert _metric("device_wait_ms_per_query", run) == pytest.approx(5.0)
    assert _metric("scan_idle_ms_per_query", run) == pytest.approx(3.5e-5)
    assert _metric("transfer_idle_ms_per_query", run) == \
        pytest.approx(1.25e-4)
    assert _metric("join_idle_ms_per_query", run) == pytest.approx(8e-5)


def test_a_program_without_spans_reads_nothing():
    """Against a program that writes no spans (no `spans` in its
    reports, no `pt.*` events in its trace) every new reader returns
    None, and none raises."""
    tr = _synthetic()
    tr.host_spans = None
    run = _run(tr, [{"phase_seconds": {}}])
    assert all(_metric(m, run) is None for m in NEW_METRICS)
    assert all(_metric(m, _run(None, [{}])) is None for m in NEW_METRICS)


def test_spans_on_the_cpu_lie_inside_the_client_query(tmp_path,
                                                      tpch_tiny):
    """One traced query at SF 0.002 through the served path: the
    program's scan, transfer and join spans lie inside the client's
    `query.q3` span, on the server's worker thread."""
    import glob
    import jax
    from bench import run as bench_run
    from bench.queries import q3
    from repro.serve import QueryServer, ServeConfig
    config = bench_run.load_json("bench", "configs", "tpch-sf1.json")
    with QueryServer(tpch_tiny, ServeConfig(**config["serve"])) as srv:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            q, _ = bench_run.run_query(srv, "q3", q3.VALIDATION,
                                       q3.plan(q3.VALIDATION))
        finally:
            jax.profiler.stop_trace()
    assert q.report is not None
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    client = [(e.start_ns, e.start_ns + e.duration_ns)
              for p in jax.profiler.ProfileData.from_file(path).planes
              for line in p.lines for e in line.events
              if e.name == "query.q3"]
    assert len(client) == 1
    lo, hi = client[0]
    host = HostSpans.load(str(tmp_path))
    assert host is not None
    for phase in spans.PHASES:
        (s, e), = host.phase_intervals(phase)
        assert lo <= s < e <= hi, phase
    names = {n for t in host.threads for n, _, _ in t}
    assert {"serve.execute", "query", "transfer.keys",
            "device.wait"} <= names
    # the client's query begins in the hand-off, before the worker's
    # serve.execute opens, and that opens before the program's query
    client_trace = SimpleNamespace(queries=[("q3", lo, hi)])
    (ex, _), = [(s, e) for t in host.threads for n, s, e in t
                if n == "serve.execute"]
    (qs, _), = [(s, e) for t in host.threads for n, s, e in t
                if n == "query"]
    assert lo < ex < qs
    assert host.name_at(client_trace, lo) == "q3.handoff"
    assert host.name_at(client_trace, ex) == "q3.serve"
    assert host.name_at(client_trace, qs).split("/")[0] in (
        "q3.query", "q3.scan")
    # the in-memory record counts what the trace shows
    rep = q.report["spans"]
    assert rep["scan"][0] == rep["transfer"][0] == rep["join"][0] == 1
    assert rep["serve.queued"][0] == 1
