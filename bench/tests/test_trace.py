"""The reduction from a profiler trace to device metrics."""
import pytest

from bench.bytes import fused_probe_least_bytes
from bench.record import Query, Run
from bench.run import load_module, BENCH
from bench.trace import Trace, union

PROBE = "%multi_probe_pallas.3 = s32[1,8,128] custom-call(u32[8,128] %f)"
BUILD = "%build_pallas.7 = u32[64,128] custom-call(s32[1024] %b)"


def _metric(name, run):
    import os
    return load_module(os.path.join(BENCH, "metrics", name + ".py")).read(run)


def _synthetic():
    """A 1000 ns window: a probe program [100, 300) whose kernel runs
    [150, 250) and whose loop [250, 300) holds a fusion, a build program
    [400, 500), and an op outside the window."""
    ops = {0: [("%fusion.1 = s32[8] fusion(s32[8] %a)", 100, 150),
               (PROBE, 150, 250), ("%while.4 = (s32[]) while(%t)", 250, 300),
               ("%fusion.5 = s32[8] fusion(s32[8] %b)", 260, 290),
               (BUILD, 400, 500), ("%fusion.9 = s32[8] fusion()", 1100, 1200)]}
    modules = {0: [("jit__fused_pallas_count(123)", 100, 300),
                   ("jit_build_pallas(456)", 400, 500)]}
    queries = [("q3", 50, 600), ("q5", 600, 1000)]
    phases = [[("scan", 1e-7), ("transfer", 3e-7), ("join", 1e-7)],
              [("scan", 4e-7)]]
    return Trace(ops, modules, queries, 0, 1000, phases)


def _run(trace, pairs):
    rep = {"phase_seconds": {}, "total_seconds": 0.0,
           "transfer": {"from_cache": False}, "device": {}}
    return Run("sf1-adhoc", 1.0, 1e-6,
               [Query("q3", {}, 1e-6, rep, rows_probed=pairs)], 0,
               {"hbm_bytes_per_s": 819e9}, trace)


def test_union_merges_overlaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_idle_and_kernel_times():
    tr = _synthetic()
    assert tr.window_s == pytest.approx(1e-6)
    assert tr.busy_s == pytest.approx(300e-9)       # [100,300) + [400,500)
    run = _run(tr, pairs=1000)
    assert _metric("device_idle_share", run) == pytest.approx(70.0)
    assert _metric("bloom_build_ms_per_query", run) == pytest.approx(1e-4)
    assert _metric("fused_probe_ms_per_query", run) == pytest.approx(2e-4)
    want = 100 * fused_probe_least_bytes(1000) / 819e9 / 200e-9
    assert _metric("fused_probe_roofline", run) == pytest.approx(want)


def test_breakdown_names_ops_by_program_and_gaps_by_host():
    out = _synthetic().breakdown()
    ops = dict(out["device_ops"])
    assert ops["jit__fused_pallas_count/%fusion"] == pytest.approx(80e-9)
    assert ops["jit__fused_pallas_count/%while"] == pytest.approx(20e-9)
    assert ops["jit__fused_pallas_count/%multi_probe_pallas"] == \
        pytest.approx(100e-9)
    assert ops["jit_build_pallas/%build_pallas"] == pytest.approx(100e-9)
    assert "?/%fusion" not in ops                   # outside the window
    gaps = out["idle_gaps"]
    assert gaps[0] == ["q5.scan", pytest.approx(500e-9)]
    assert ["q3.transfer", pytest.approx(100e-9)] in gaps
    assert ["q3.scan", pytest.approx(100e-9)] in gaps


def test_no_trace_no_device_metrics():
    run = _run(None, pairs=1000)
    for name in ("device_idle_share", "fused_probe_roofline",
                 "bloom_build_ms_per_query", "fused_probe_ms_per_query",
                 "segjoin_ms_per_query"):
        assert _metric(name, run) is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace recorded on a TPU v5 lite: one Q3 (SF 0.01, validation
    parameters but segment MACHINERY) through the served path, inside
    `bench.window` and `query.q3` spans; its transfer probed 40498
    (key, filter) pairs."""
    import gzip
    import os
    from bench.run import ROOT
    src = os.path.join(ROOT, "bench", "tests", "data",
                       "q3_sf001.xplane.pb.gz")
    root = tmp_path_factory.mktemp("trace")
    dest = root / "plugins" / "profile" / "run"
    dest.mkdir(parents=True)
    with gzip.open(src, "rb") as f:
        (dest / "host.xplane.pb").write_bytes(f.read())
    return root


def _events(root, plane_name, line_name):
    import glob
    import jax
    path = glob.glob(str(root / "**" / "*.xplane.pb"), recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            if (plane.name == plane_name or plane_name is None) \
                    and line.name == line_name:
                yield from ((e.name, e.start_ns, e.duration_ns)
                            for e in line.events)


def test_recorded_trace(recorded):
    tr = Trace.load(str(recorded))
    window = [(s, d) for n, s, d in _events(recorded, None, "python3")
              if n == "bench.window"]
    assert len(window) == 1
    assert tr.window_s == pytest.approx(window[0][1] / 1e9)
    ops = list(_events(recorded, "/device:TPU:0", "XLA Ops"))
    assert ops
    # busy: no more than the sum of top-level programs, no less than any
    mods = [d for _, s, d in _events(recorded, "/device:TPU:0",
                                     "XLA Modules")]
    assert max(mods) / 1e9 <= tr.busy_s <= sum(mods) / 1e9 + 1e-12
    probe = sum(d for n, _, d in _events(recorded, "/device:TPU:0",
                                         "XLA Modules")
                if n.startswith("jit__fused_pallas_")) / 1e9
    build = sum(d for n, _, d in ops if n.startswith("%build_pallas")
                and "custom-call" in n) / 1e9
    assert probe > 0 and build > 0
    run = _run(tr, pairs=40498)
    share = _metric("fused_probe_roofline", run)
    want = 100 * fused_probe_least_bytes(40498) / 819e9 / probe
    assert share == pytest.approx(want) and 0 < share <= 100
    assert _metric("bloom_build_ms_per_query", run) == \
        pytest.approx(1e3 * build)
    idle = _metric("device_idle_share", run)
    assert 0 < idle < 100
    out = tr.breakdown()
    assert 0 < len(out["device_ops"]) <= 10
    assert all(name.startswith("q3.") for name, _ in out["idle_gaps"]) \
        or out["idle_gaps"] == []
