"""The reader of `segjoin_narrow_share`: the share of the window's
segment joins searched on one key plane, read from the program's
counters, or nothing where a report lacks them."""
from bench import run as bench_run
from bench.record import Query, Run

NAME = "segjoin_narrow_share"


def _reader():
    return bench_run.load_module(f"{bench_run.BENCH}/metrics/{NAME}.py")


def _run(devices):
    queries = [Query("q9", {}, 1.0, {"device": d}) for d in devices]
    return Run("sf1-dashboard", 1.0, 2.0, queries, 0, {})


def test_listed_for_every_cell():
    spec = bench_run.load_json("BENCHMARK.json")
    (m,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert m["workloads"] == ["sf1-adhoc", "sf1-dashboard", "sf3-adhoc"]
    assert set(m["workloads"]) <= {w["name"] for w in spec["workloads"]}
    assert (m["layer"], m["moves"], m["unit"]) == ("kernels", "qps", "%")


def test_reads_the_counters():
    reader = _reader()
    assert reader.read(_run([{"round_trips": 3}] * 2)) is None
    assert reader.read(_run([{"segjoin_calls": 0,
                              "segjoin_narrow": 0}])) is None
    joined = _run([{"segjoin_calls": 5, "segjoin_narrow": 4},
                   {"segjoin_calls": 3, "segjoin_narrow": 2}])
    assert reader.read(joined) == 75.0
