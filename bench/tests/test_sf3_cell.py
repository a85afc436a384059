"""The `sf3-adhoc` cell: it resolves to the `tpch-sf3` configuration
under `adhoc` traffic, a run of it on the CPU at a tiny scale factor
(Pallas in interpret mode, the Bloom engine's device-resident plane on
as on a TPU) comes out correct, and the readers of the fused probes'
walk read the program's counters, or nothing where a report lacks
them."""
import argparse

import pytest

from bench import run as bench_run
from bench.record import Query, Run
from bench.tests.test_harness import _cell

WALK_METRICS = ("probe_tiles_per_query", "probe_walk_share")


def test_cell_resolves():
    spec, cell, config, traffic = bench_run.resolve("sf3-adhoc")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("tpch-sf3", "adhoc", 1)
    assert config["sf"] == 3 and config["reduced"] == ["sf"]
    assert config["serve"] == {"strategy": "pred-trans",
                               "join_backend": "pallas", "degrade": False,
                               "workers": 1}
    assert traffic["params"] == "fresh"
    for m in spec["per_layer"]:
        if m["name"] in WALK_METRICS:
            assert m["workloads"] == ["sf1-adhoc", "sf3-adhoc"]


def test_sound_run_is_correct(monkeypatch):
    runs = []
    real = bench_run.read_metrics

    def read_metrics(spec, name, run, trace):
        runs.append(run)
        return real(spec, name, run, trace)
    monkeypatch.setattr(bench_run, "read_metrics", read_metrics)
    spec, cell, config, traffic = _cell("sf3-adhoc")
    # the fused probes walk only on the device-resident plane, the Bloom
    # engine's default on a TPU
    config["serve"]["strategy_kw"] = {"device_resident": True}
    args = argparse.Namespace(workload="sf3-adhoc", seed=2**31 + 77,
                              seconds=0.2, trace=0)
    out = bench_run.measure(args, spec, cell, config, traffic,
                            on_chip=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 5 and out["failed"] == 0
    assert not any(q.report["transfer"]["from_cache"]
                   for q in runs[0].done)
    dev = [q.report["device"] for q in runs[0].done]
    assert sum(d["probe_tiles"] for d in dev) > 0
    assert all(d["probe_rows_walked"] <= d["probe_width"] for d in dev)


def _run(devices):
    queries = [Query("q9", {}, 1.0, {"device": d}) for d in devices]
    return Run("sf3-adhoc", 1.0, 2.0, queries, 0, {})


@pytest.mark.parametrize("name", WALK_METRICS)
def test_walk_readers(name):
    reader = bench_run.load_module(
        f"{bench_run.BENCH}/metrics/{name}.py")
    assert reader.read(_run([{"round_trips": 3}] * 2)) is None
    walked = _run([{"probe_tiles": 3, "probe_rows_walked": 3 << 18,
                    "probe_width": 1 << 20},
                   {"probe_tiles": 1, "probe_rows_walked": 1 << 18,
                    "probe_width": 1 << 20}])
    want = {"probe_tiles_per_query": 2.0, "probe_walk_share": 50.0}
    assert reader.read(walked) == want[name]
    idle = _run([{"probe_tiles": 0, "probe_rows_walked": 0,
                  "probe_width": 0}])
    assert reader.read(idle) == {"probe_tiles_per_query": 0.0,
                                 "probe_walk_share": None}[name]
