"""The harness end to end on the CPU at a tiny scale factor, with Pallas
in interpret mode: it refuses the CPU as a chip, a sound run comes out
correct, and a run whose timed path is broken underneath comes out not
correct, once for each fault the cells can have."""
import argparse
import copy

import numpy as np
import pytest

from bench import run as bench_run


def _cell(workload):
    spec, cell, config, traffic = bench_run.resolve(workload)
    config = copy.deepcopy(config)
    config["sf"] = 0.002
    return spec, cell, config, traffic


def _measure(workload, seed=2**31 + 5):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.2,
                              trace=0)
    return bench_run.measure(args, *_cell(workload), on_chip=False)


def test_refuses_the_cpu(capsys):
    rc = bench_run.main(["--workload", "sf1-adhoc", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0 and out == "" and "no TPU" in err


def test_unknown_device_kind_raises():
    assert bench_run.chip_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(bench_run.BenchError, match="not in bench/peaks"):
        bench_run.chip_peaks("TPU v99")


@pytest.mark.parametrize("workload,replayed", [("sf1-adhoc", False),
                                               ("sf1-dashboard", True)])
def test_sound_run_is_correct(workload, replayed, monkeypatch):
    """A sound run is correct; the ad-hoc window never hits the slot
    cache (its queries were rehearsed on another server), the dashboard
    window always does."""
    runs = []
    real = bench_run.read_metrics

    def read_metrics(spec, name, run, trace):
        runs.append(run)
        return real(spec, name, run, trace)
    monkeypatch.setattr(bench_run, "read_metrics", read_metrics)
    out = _measure(workload)
    assert {q.report["transfer"]["from_cache"] for q in runs[0].done} \
        == {replayed}
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 5 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    spec = bench_run.load_json("BENCHMARK.json")
    names = {m["name"] for m in spec["end_to_end"]}
    assert set(out["metrics"]) == names
    assert all(v["value"] > 0 for v in out["metrics"].values())


def _alter_answer(monkeypatch):
    """An answer altered where it is produced: one money value."""
    from repro.serve import QueryServer
    real = QueryServer.query

    def query(self, plan, *a, **kw):
        res, stats = real(self, plan, *a, **kw)
        name = [n for n in res.names
                if res[n].dictionary is None
                and res[n].data.dtype.kind == "f"]
        if name and len(res):
            from repro.relational.table import Column
            data = res[name[0]].data.copy()
            data[0] *= 1.0 + 1e-6
            res = res.with_column(name[0], Column(data))
        return res, stats
    monkeypatch.setattr(QueryServer, "query", query)


def _drop_survivors(monkeypatch):
    """Transfer drops rows: every other survivor of each Bloom probe is
    lost, as after a false negative."""
    import jax.numpy as jnp
    from repro.core.engine_bloom import PallasEngine
    real = PallasEngine.probe_idx

    def probe_idx(self, *a, **kw):
        ok = real(self, *a, **kw)
        return ok & (jnp.arange(ok.shape[0]) % 2 == 0)
    monkeypatch.setattr(PallasEngine, "probe_idx", probe_idx)


def _stale_answer(monkeypatch):
    """An answer for other parameters: each query of a template gets the
    first answer that template ever gave."""
    from repro.serve import QueryServer
    real = QueryServer.query
    first = {}

    def query(self, plan, *a, **kw):
        res, stats = real(self, plan, *a, **kw)
        key = tuple(res.names)
        return first.setdefault(key, res), stats
    monkeypatch.setattr(QueryServer, "query", query)


# a stale answer is the right one where parameters are fixed
@pytest.mark.parametrize("workload,fault", [
    ("sf1-adhoc", _alter_answer), ("sf1-adhoc", _drop_survivors),
    ("sf1-adhoc", _stale_answer), ("sf1-dashboard", _alter_answer),
    ("sf1-dashboard", _drop_survivors)])
def test_broken_path_is_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    out = _measure(workload, seed=4242)
    assert not out["correct"], out["checks"]


def test_control_run_is_not_correct(monkeypatch):
    """The control in the program's place: every answer the window
    records is the plain reference's, computed in float32."""
    from bench.correct import plain_tables
    from bench.reference import Reference
    real = bench_run.run_query
    refs = {}

    def run_query(srv, template, params, plan):
        q, answer = real(srv, template, params, plan)
        if answer is not None:
            ctl = refs.setdefault(id(srv), Reference(
                plain_tables(srv.catalog), np.float32))
            answer = ctl.answer(template, params)
        return q, answer
    monkeypatch.setattr(bench_run, "run_query", run_query)
    out = _measure("sf1-adhoc", seed=99)
    assert not out["correct"], out["checks"]
