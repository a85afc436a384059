"""The benchmark's query templates, their parameter stream and their
plain reference, on the CPU at a tiny scale factor."""
import importlib

import numpy as np
import pytest

from bench.correct import compare, plain_tables
from bench.reference import Reference

TEMPLATES = {"q3": 3, "q5": 5, "q9": 9, "q17": 17, "q21": 21}
# qgen domain sizes (TPC-H v3 clause 2.4)
DOMAIN = {"q3": 5 * 31, "q5": 5 * 5, "q9": 92, "q17": 25 * 40, "q21": 25}


def _template(name):
    return importlib.import_module(f"bench.queries.{name}")


def _oracle(cat, plan):
    from repro.core.transfer import make_strategy
    from repro.relational.executor import ExecConfig, Executor
    cfg = ExecConfig(strategy=make_strategy("no-pred-trans"),
                     join_backend="numpy", late_materialize=False)
    return Executor(cat, cfg).execute(plan)[0]


@pytest.mark.parametrize("name", sorted(TEMPLATES))
def test_validation_parameters_give_the_repo_plan(name, tpch_small):
    """With the specification's validation parameters a template's
    answer is md5-equal to `repro.tpch.build_query(N)`'s at SF 0.01."""
    from repro.relational.table import table_digest
    from repro.tpch import build_query
    mod = _template(name)
    got = _oracle(tpch_small, mod.plan(mod.VALIDATION))
    want = _oracle(tpch_small, build_query(TEMPLATES[name], sf=0.01))
    assert table_digest(got) == table_digest(want)


@pytest.mark.parametrize("name", sorted(TEMPLATES))
def test_domain_is_qgen_s(name):
    dom = _template(name).domain()
    assert len(dom) == DOMAIN[name]
    assert len({tuple(sorted(p.items())) for p in dom}) == len(dom)
    assert _template(name).VALIDATION in dom


@pytest.fixture(scope="module")
def served(tpch_tiny):
    """Two seeded draws of every template, answered by a `QueryServer`
    on the benchmark's settings (Pallas in interpret mode here)."""
    import json
    import os
    from repro.serve import QueryServer, ServeConfig
    from bench.run import ROOT, Stream
    with open(os.path.join(ROOT, "bench/configs/tpch-sf1.json")) as f:
        serve = json.load(f)["serve"]
    with open(os.path.join(ROOT, "bench/traffic/adhoc.json")) as f:
        traffic = json.load(f)
    stream = Stream(traffic, 12345678901)
    out = []
    with QueryServer(tpch_tiny, ServeConfig(**serve)) as srv:
        for i in range(2):
            for t, p, plan in stream.cycle(i):
                out.append((t, p, srv.query(plan)[0].to_pydict()))
    return out


def test_reference_equals_the_served_answers(served, tpch_tiny):
    ref = Reference(plain_tables(tpch_tiny))
    for t, p, got in served:
        assert compare(got, ref.answer(t, p)) == (0, 0.0), (t, p)


def test_control_fails_the_limit(served, tpch_tiny):
    """The reference computed in float32 — the control — is refused by
    the configuration's limit in every cycle of the stream: one money
    answer of the cycle at least lies outside it."""
    import json
    import os
    from bench.run import ROOT
    ref = Reference(plain_tables(tpch_tiny))
    ctl = Reference(plain_tables(tpch_tiny), np.float32)
    for cfg in ("tpch-sf1", "tpch-sf3"):
        with open(os.path.join(ROOT, f"bench/configs/{cfg}.json")) as f:
            limit = json.load(f)["limits"]["value_rel_gap"]
        for c in range(0, len(served), len(TEMPLATES)):
            worst = max(compare(ctl.answer(t, p), ref.answer(t, p))[1]
                        for t, p, _ in served[c: c + len(TEMPLATES)])
            assert worst > limit, (cfg, c, worst)


def test_fresh_draws_never_repeat_and_every_seed_runs_one_set():
    """`fresh` parameters: no draw repeats within a run's cycles, every
    seed sends the same set in its own order; `fixed`: one draw per
    template, the same every cycle."""
    from bench.run import Stream
    traffic = {"templates": list(TEMPLATES), "params": "fresh",
               "set_seed": 5, "cycles": 6}

    def draws(seed):
        stream = Stream(traffic, seed)
        return [(t, tuple(sorted(p.items()))) for i in range(6)
                for t, p, _ in stream.cycle(i)]
    a, b = draws(2**31 + 12345), draws(7)
    assert len(set(a)) == len(a) == 30
    assert set(a) == set(b) and a != b
    with pytest.raises(IndexError):
        Stream(traffic, 7).cycle(6)
    fixed = Stream(dict(traffic, params="fixed"), 7)
    first = [p for _, p, _ in fixed.cycle(0)]
    assert [p for _, p, _ in fixed.cycle(9)] == first
