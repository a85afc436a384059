"""Device-resident data plane (DESIGN.md §15):

* `DeviceStats` accounting: crossings count only inside `track()`,
  round trips total the h2d + d2h syncs, subquery merge adds through.
* Fused vertex scans (jax + pallas-interpret) vs the numpy host oracle:
  probe -> min-max range cut -> key-range -> build over one survivor
  set, filter words and masks bit-exact.
* The device sorted-segment join vs the engine NULL-contract reference
  (`JoinEngine.join_indices_valid`): a deterministic seeded sweep that
  always runs (duplicate keys, NULL keys on both sides, empty survivor
  sets, signed-extreme keys, all `how` modes) plus a hypothesis
  strategy when the package is present.
* TPC-H: all 20 queries bit-exact with the device plane forced on
  (jax at sf 0.01 under pred-trans and pred-trans-adaptive,
  pallas-interpret at sf 0.002), and the aggregate host<->device
  round-trip count must beat the legacy per-op path on the wide-join
  queries.
* Artifact-cache eviction: cost-to-rebuild weighting (cheap and
  unknown-cost artifacts go first, ties keep LRU order).
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                     # property tests skip, rest run
    HAVE_HYPOTHESIS = False

    def given(*a, **kw):
        return lambda f: pytest.mark.skip("hypothesis missing")(f)

    def settings(*a, **kw):
        return lambda f: f

    class st:
        @staticmethod
        def lists(*a, **kw):
            return None

        @staticmethod
        def integers(*a, **kw):
            return None

        @staticmethod
        def sampled_from(*a, **kw):
            return None

        @staticmethod
        def booleans():
            return None

from repro.core import bloom, device_plane  # noqa: E402
from repro.core.artifact_cache import ArtifactCache  # noqa: E402
from repro.core.engine_bloom import get_engine  # noqa: E402
from repro.core.engine_join import NumpyJoinEngine  # noqa: E402
from repro.core.transfer import make_strategy  # noqa: E402
from repro.kernels.semijoin import ops as sj  # noqa: E402
from repro.relational import ExecConfig, Executor  # noqa: E402
from repro.tpch import QUERIES, build_query  # noqa: E402

HOWS = ("inner", "left", "semi", "anti")


def _assert_tables_exact(a, b, ctx):
    """Bitwise equality of all observable values (NULL rows'
    representative payload bytes are unspecified and excluded)."""
    assert a.names == b.names, ctx
    assert len(a) == len(b), (ctx, len(a), len(b))
    for n in a.names:
        va = a[n].valid if a[n].valid is not None \
            else np.ones(len(a), bool)
        vb = b[n].valid if b[n].valid is not None \
            else np.ones(len(b), bool)
        np.testing.assert_array_equal(va, vb, err_msg=str((ctx, n)))
        np.testing.assert_array_equal(a[n].data[va], b[n].data[vb],
                                      err_msg=str((ctx, n)))


# --------------------------------------------------------------------------
# DeviceStats accounting
# --------------------------------------------------------------------------


def test_device_stats_counts_only_inside_track():
    stats = device_plane.DeviceStats()
    a = np.arange(1024, dtype=np.int64)
    with device_plane.track(stats):
        d = device_plane.to_device(a)           # host -> device: counted
        device_plane.to_device(d)               # already device: free
        h = device_plane.to_host(d)             # device -> host: counted
        device_plane.to_host(h)                 # already host: free
    assert stats.h2d_syncs == 1
    assert stats.h2d_bytes == a.nbytes
    assert stats.d2h_syncs == 1
    assert stats.round_trips() == 2             # total crossings
    device_plane.to_device(a)                   # outside track(): free
    assert stats.h2d_syncs == 1


def test_device_stats_merge_and_report():
    a, b = device_plane.DeviceStats(), device_plane.DeviceStats()
    with device_plane.track(a):
        device_plane.to_device(np.zeros(8, np.int64))
        device_plane.count_fused()
        device_plane.count_segjoin(narrow=True)
    with device_plane.track(b):
        device_plane.to_host(device_plane.to_device(np.zeros(4, np.int64)))
        device_plane.count_segjoin(narrow=False)
        with device_plane.span("transfer"):
            pass
    a.merge(b)
    rep = a.report()
    assert rep["h2d_syncs"] == 2
    assert rep["d2h_syncs"] == 1
    assert rep["round_trips"] == 3              # h2d + d2h
    assert rep["fused_calls"] == 1
    assert (rep["segjoin_calls"], rep["segjoin_narrow"]) == (2, 1)
    assert "device_compactions" not in rep
    spans = a.span_report()
    assert spans["device.upload"][0] == 2       # one each, merged
    assert spans["device.wait"][0] == 1
    assert spans["transfer"][0] == 1


def test_track_restores_previous_context():
    outer, inner = device_plane.DeviceStats(), device_plane.DeviceStats()
    with device_plane.track(outer):
        with device_plane.track(inner):
            device_plane.to_device(np.zeros(2, np.int64))
        device_plane.to_device(np.zeros(2, np.int64))
    assert inner.h2d_syncs == 1
    assert outer.h2d_syncs == 1


def test_spans_nest():
    stats = device_plane.DeviceStats()
    with device_plane.track(stats):
        with device_plane.span("join") as outer:
            with device_plane.span("join.aggregate") as inner:
                with device_plane.span("join.materialize") as innermost:
                    sum(range(1000))
            with device_plane.span("join.aggregate"):
                pass
    assert 0 < innermost.ns <= inner.ns <= outer.ns
    assert stats.spans["join"] == [1, outer.ns]
    assert stats.spans["join.aggregate"][0] == 2
    assert stats.spans["join.aggregate"][1] >= inner.ns
    assert stats.spans["join.materialize"] == [1, innermost.ns]
    assert outer.seconds == outer.ns / 1e9


def test_spans_are_noops_outside_track():
    stats = device_plane.DeviceStats()
    with device_plane.span("scan") as sp:
        device_plane.to_host(device_plane.to_device(np.zeros(4)))
    assert sp.ns == 0 and sp.seconds == 0.0
    assert stats.spans == {} and device_plane.active() is None


def test_only_to_host_and_scalar_sync():
    """The d2h paths are `to_host` and `scalar`, each one counted sync
    inside a `device.wait` span; the bare counters are gone."""
    assert not hasattr(device_plane, "count_d2h")
    assert not hasattr(device_plane, "count_h2d")
    assert not hasattr(device_plane, "count_compaction")
    stats = device_plane.DeviceStats()
    with device_plane.track(stats):
        d = device_plane.to_device(np.arange(8, dtype=np.int32))
        assert device_plane.scalar(d.sum()) == 28
        device_plane.to_host(d)
    assert stats.d2h_syncs == 2 and stats.h2d_syncs == 1
    assert stats.spans["device.wait"][0] == 2
    assert stats.spans["device.upload"][0] == 1


@pytest.mark.parametrize("qn", [3, 17])
def test_spans_reach_report_and_phase_seconds(tpch_tiny, qn):
    """`report()["spans"]` counts and times every span, subqueries
    folded in; `phase_seconds` is each phase's own span (Q17's subquery
    runs inside the outer scan, its phases are its own)."""
    res, stats = Executor(tpch_tiny, _device_cfg("pred-trans", "jax",
                                                 "on")).execute(
        build_query(qn, sf=0.002))
    rep = stats.report()
    spans = rep["spans"]
    nsub = len(stats.subqueries)
    assert spans["query"][0] == 1 + nsub
    for phase in ("scan", "transfer", "join"):
        assert spans[phase][0] == 1 + nsub
        own = spans[phase][1] - sum(s.report()["spans"][phase][1]
                                    for s in stats.subqueries)
        assert rep["phase_seconds"][phase] == pytest.approx(own, abs=1e-9)
    assert list(rep["phase_seconds"]) == ["scan", "transfer", "join"]
    assert rep["total_seconds"] == pytest.approx(
        sum(rep["phase_seconds"].values()))
    assert spans["query"][1] >= rep["total_seconds"]
    assert spans["device.wait"][0] == rep["device"]["d2h_syncs"]
    assert spans["device.upload"][0] == rep["device"]["h2d_syncs"]
    assert spans.get("subquery", [0])[0] == nsub
    assert (nsub > 0) == (qn == 17)


def test_server_reports_queue_wait(tpch_tiny):
    from repro.serve import QueryServer, ServeConfig
    with QueryServer(tpch_tiny, ServeConfig(strategy="pred-trans",
                                            workers=1)) as srv:
        _, stats = srv.query(build_query(3, sf=0.002))
    count, secs = stats.report()["spans"]["serve.queued"]
    assert count == 1 and 0 <= secs < stats.report()["spans"]["query"][1]


# --------------------------------------------------------------------------
# fused vertex scans: device backends vs the numpy host oracle
# --------------------------------------------------------------------------


def _engine(backend):
    if backend == "jax-tpu":
        # the routing JaxEngine takes on a TPU: device-resident scans,
        # device compaction and device filter builds
        from repro.core.engine_bloom import JaxEngine
        eng = JaxEngine(device_resident=True)
        assert not (eng.host_build or eng.host_compact)
        return eng
    return get_engine(backend)


def _scan_outputs(backend, mask, keys, keys2, raw, out_keys, valid,
                  words1, words2, nblocks):
    eng = _engine(backend)
    scan = eng.begin(mask)
    scan.probe([(words1, eng.keys(keys)), (words2, eng.keys(keys2))])
    after_probe = np.asarray(device_plane.to_host(scan.mask)).copy()
    live_after = list(scan.live_after)
    scan.probe_range(raw, -120, 340, ek=eng.keys(raw))
    kr = scan.key_range(raw, ek=eng.keys(raw))
    krv = scan.key_range(raw, ek=eng.keys(raw), valid=valid)
    words = scan.build(eng.keys(out_keys), nblocks, valid=valid)
    return {"after_probe": after_probe, "live_after": live_after,
            "mask": np.asarray(device_plane.to_host(scan.mask)).copy(),
            "live": int(scan.live), "key_range": kr,
            "key_range_valid": krv,
            "words": np.asarray(device_plane.to_host(words)).copy()}


@pytest.mark.parametrize("backend", ["jax", "pallas", "jax-tpu"])
def test_fused_scan_matches_numpy_oracle(rng, backend):
    """One fused probe->range-cut->build scan, bit-exact vs the host
    engine: surviving mask after each stage, per-filter live counts,
    device key ranges (plain and NULL-masked), emitted filter words."""
    n = 600 if backend == "pallas" else 3000
    keys = rng.integers(0, 900, n).astype(np.int64)
    keys2 = rng.integers(0, 900, n).astype(np.int64)
    raw = rng.integers(-500, 500, n).astype(np.int64)
    out_keys = rng.integers(0, 900, n).astype(np.int64)
    mask = rng.random(n) < 0.8
    valid = rng.random(n) < 0.9
    nblocks = bloom.blocks_for(n)
    host = get_engine("numpy")
    words1 = np.asarray(host.build_filter(
        host.keys(rng.integers(0, 900, 500).astype(np.int64))).words)
    words2 = np.asarray(host.build_filter(
        host.keys(rng.integers(0, 900, 700).astype(np.int64))).words)
    args = (mask, keys, keys2, raw, out_keys, valid, words1, words2,
            nblocks)
    ref = _scan_outputs("numpy", *args)
    got = _scan_outputs(backend, *args)
    for field in ref:
        np.testing.assert_array_equal(
            np.asarray(got[field], dtype=object)
            if field.startswith("key_range") else got[field],
            np.asarray(ref[field], dtype=object)
            if field.startswith("key_range") else ref[field],
            err_msg=f"{backend}/{field}")


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_fused_scan_empty_survivors(rng, backend):
    """A disjoint range cut kills every row: the scan must report an
    empty live set, key_range None, and an all-zero outgoing filter —
    same as the host engine."""
    n = 256
    keys = rng.integers(0, 50, n).astype(np.int64)
    raw = rng.integers(0, 50, n).astype(np.int64)
    nblocks = bloom.blocks_for(n)
    outs = {}
    for b in ("numpy", backend):
        eng = get_engine(b)
        scan = eng.begin(np.ones(n, bool))
        scan.probe_range(raw, 1000, 2000, ek=eng.keys(raw))
        words = scan.build(eng.keys(keys), nblocks)
        outs[b] = (int(scan.live), scan.key_range(raw, ek=eng.keys(raw)),
                   np.asarray(device_plane.to_host(words)).copy())
    assert outs[backend][0] == outs["numpy"][0] == 0
    assert outs[backend][1] is None and outs["numpy"][1] is None
    np.testing.assert_array_equal(outs[backend][2], outs["numpy"][2])


# --------------------------------------------------------------------------
# device sorted-segment join vs the engine NULL-contract reference
# --------------------------------------------------------------------------


def _check_segjoin(bk, pk, how, bv=None, pv=None):
    eb, ep = NumpyJoinEngine().join_indices_valid(bk, pk, how, bv, pv)
    gb, gp = sj.segment_join_device(bk, pk, how, bv, pv)
    gb = np.asarray(device_plane.to_host(gb)).astype(np.int64)
    gp = np.asarray(device_plane.to_host(gp)).astype(np.int64)
    ctx = (how, len(bk), len(pk), bv is not None, pv is not None)
    np.testing.assert_array_equal(gb, eb, err_msg=str(ctx))
    np.testing.assert_array_equal(gp, ep, err_msg=str(ctx))


EXTREMES = np.array([np.iinfo(np.int64).min, -(1 << 62), -3, -1, 0, 1,
                     7, 1 << 31, (1 << 62) - 1, np.iinfo(np.int64).max],
                    np.int64)


@pytest.mark.parametrize("how", HOWS)
def test_segment_join_device_seeded_sweep(how):
    """Always-on property sweep: heavy duplicate keys, NULL keys on
    either side, signed-extreme key values."""
    rng = np.random.default_rng(42)
    for trial in range(25):
        nb = int(rng.integers(1, 70))
        npr = int(rng.integers(1, 90))
        if trial % 5 == 4:              # signed-extreme key mix
            bk = rng.choice(EXTREMES, nb)
            pk = rng.choice(EXTREMES, npr)
        else:
            dom = int(rng.integers(1, 14))
            bk = rng.integers(0, dom, nb).astype(np.int64)
            pk = rng.integers(0, dom, npr).astype(np.int64)
        bv = (rng.random(nb) < 0.75) if rng.random() < 0.5 else None
        pv = (rng.random(npr) < 0.75) if rng.random() < 0.5 else None
        _check_segjoin(bk, pk, how, bv, pv)


@pytest.mark.parametrize("how", HOWS)
def test_segment_join_device_empty_survivors(how):
    """All-NULL sides: no probe row may match; inner/semi emit nothing,
    left emits unmatched, anti keeps every live probe row."""
    bk = np.array([5, 5, 9], np.int64)
    pk = np.array([5, 9, 9, 11], np.int64)
    _check_segjoin(bk, pk, how, np.zeros(3, bool), None)
    _check_segjoin(bk, pk, how, None, np.zeros(4, bool))
    _check_segjoin(bk, pk, how, np.zeros(3, bool), np.zeros(4, bool))


@pytest.mark.parametrize("how", HOWS)
def test_segment_join_device_heavy_duplicates(how):
    """Match runs longer than 127 rows: the emission's running count
    takes more than one 7-bit digit of `bloom.prefix_sum`."""
    rng = np.random.default_rng(5)
    bk = rng.integers(0, 3, 700).astype(np.int64)
    pk = rng.integers(0, 4, 300).astype(np.int64)
    _check_segjoin(bk, pk, how, rng.random(700) < 0.9, None)


def _key_plane_case(case):
    """(build keys, probe keys, build validity, probe validity, whether
    the live build keys share one high half) for one key layout."""
    rng = np.random.default_rng(sum(map(ord, case)))
    if case == "narrow":            # one column below 2^32, lo past 2^31
        bk = rng.integers(0, 50, 300) * 80_000_000
        pk = rng.integers(0, 60, 200) * 80_000_000
        return bk, pk, None, None, True
    if case == "wide":              # packed composite (a << 32) | b
        bk = (rng.integers(0, 5, 300) << 32) | rng.integers(0, 6, 300)
        pk = (rng.integers(0, 6, 200) << 32) | rng.integers(0, 7, 200)
        return bk, pk, None, rng.random(200) < 0.8, False
    if case == "uniform_hi":        # probes of another high half miss
        bk = (7 << 32) | rng.integers(0, 20, 300)
        pk = (rng.choice([7, 9], 200) << 32) | rng.integers(0, 20, 200)
        return bk, pk, None, None, True
    if case == "negative":          # high half 0xFFFFFFFF on every key
        bk = -rng.integers(1, 40, 300)
        neg = -rng.integers(1, 45, 200)
        pk = np.where(rng.random(200) < 0.5, neg, neg & 0xFFFFFFFF)
        return bk, pk, None, None, True
    if case == "run_at_end":        # the largest key's run ends the live rows
        bk = np.array([9, 1, 9, 3, 9, 20, 20, 9, 0])
        bv = np.array([1, 1, 1, 1, 1, 0, 0, 1, 1], bool)
        pk = np.array([9, 20, 1, 5, 9, 0, 21, -9])
        return bk, pk, bv, None, True
    if case == "null_tail":         # a live prefix, then NULL-key rows
        bk = np.array([4, 4, 2, 8, 6, 6, 1, 3, 5, 7])
        pk = np.arange(10)
        return bk, pk, np.arange(10) < 6, None, True
    assert case == "no_live"        # nb_live = 0
    bk, pk = np.array([1, 2, 2]), np.array([2, 1, 0])
    return bk, pk, np.zeros(3, bool), None, True


@pytest.mark.parametrize("how", HOWS)
@pytest.mark.parametrize("case", ["narrow", "wide", "uniform_hi",
                                  "negative", "run_at_end", "null_tail",
                                  "no_live"])
def test_segment_join_device_key_planes(case, how):
    """One lower-bound search over the live build rows, on one key plane
    where the live build keys share their high half and on two where
    they do not, against the NULL-contract reference; the counters say
    which it searched."""
    bk, pk, bv, pv, narrow = _key_plane_case(case)
    stats = device_plane.DeviceStats()
    with device_plane.track(stats):
        _check_segjoin(bk.astype(np.int64), pk.astype(np.int64), how, bv,
                       pv)
    assert (stats.segjoin_calls, stats.segjoin_narrow) == (1, int(narrow))


@pytest.mark.parametrize("n", [64, 1000, 1 << 14])
def test_prefix_sum_and_flatnonzero_match_numpy(n):
    """The scatter- and cumsum-free device primitives vs numpy: exact
    running sums of multi-digit values, and sized nonzero with its
    zero fill."""
    import jax.numpy as jnp
    rng = np.random.default_rng(n)
    vals = rng.integers(0, 1 << 16, n).astype(np.int32)  # sum < 2^31
    np.testing.assert_array_equal(
        np.asarray(bloom.prefix_sum(jnp.asarray(vals))), np.cumsum(vals))
    ok = rng.random(n) < 0.3
    for size in (n, n // 4):
        want = np.zeros(size, np.int64)
        hits = np.flatnonzero(ok)[:size]
        want[:len(hits)] = hits
        np.testing.assert_array_equal(
            np.asarray(bloom.flatnonzero(jnp.asarray(ok), size)), want)


@pytest.mark.parametrize("n,size,live", [
    (1 << 12, 1 << 8, 100),             # size < n
    (1 << 12, 1 << 12, 1000),           # size = n
    (1 << 12, 64, 0),                   # no survivors
    (1 << 12, 1 << 8, 1 << 8),          # survivors exactly fill size
])
def test_fused_compactions_match_numpy(n, size, live):
    """The fused probe's survivor compaction into `size` slots, count
    (mask positions) and gather (`idx` at them), on both device engines:
    front-packed ascending positions with zero fill, as
    `np.flatnonzero`."""
    import jax.numpy as jnp
    rng = np.random.default_rng(n + size + live)
    ok = np.zeros(n, bool)
    ok[rng.choice(n, live, replace=False)] = True
    idx = rng.permutation(1 << 14)[:n].astype(np.int32)
    want = np.zeros(size, np.int64)
    want[:live] = np.flatnonzero(ok)
    for backend in ("jax", "pallas"):
        eng = get_engine(backend, device_resident=True)
        np.testing.assert_array_equal(
            np.asarray(eng.fused_compact(jnp.asarray(ok), None, size)),
            want)
        np.testing.assert_array_equal(
            np.asarray(eng.fused_compact(jnp.asarray(ok),
                                         jnp.asarray(idx), size)),
            idx[want])


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("start", ["all", "some"])
@pytest.mark.parametrize("case", ["shrinks", "stays", "removes_nothing"])
def test_fused_probe_compacts_into_survivor_bucket(backend, start, case):
    """A device-resident fused probe (count variant from an all-live
    start, gather variant from a partial one) vs the host mirror: the
    same survivor ids and per-filter live counts whether the survivors'
    bucket shrinks, stays, or no row is removed; the survivor ids fill
    their own bucket, and a probe that removes nothing dispatches no
    compaction."""
    n = 3000
    rng = np.random.default_rng(15)
    keys = rng.permutation(n).astype(np.int64)          # distinct
    kept = {"shrinks": 100, "stays": 2700, "removes_nothing": n}[case]
    mask = np.ones(n, bool) if start == "all" else rng.random(n) < 0.97
    host = get_engine("numpy")
    words = np.asarray(host.build_filter(
        host.keys(np.arange(kept, dtype=np.int64))).words)
    every = np.asarray(host.build_filter(host.keys(keys)).words)
    outs = {}
    for b in ("numpy", backend):
        eng = host if b == "numpy" else get_engine(b, device_resident=True)
        stats = device_plane.DeviceStats()
        with device_plane.track(stats):
            scan = eng.begin(mask)
            scan.probe([(words, eng.keys(keys)), (every, eng.keys(keys))])
        outs[b] = (np.flatnonzero(scan.mask), list(scan.live_after))
    ids, live_after = outs[backend]
    np.testing.assert_array_equal(ids, outs["numpy"][0])
    assert live_after == outs["numpy"][1]
    width = eng.bucket(int(mask.sum()))
    if case == "removes_nothing":
        assert stats.compact_calls == 0
    else:
        assert stats.compact_calls == 1
        assert (eng.bucket(len(ids)) < width) == (case == "shrinks")
        assert stats.compact_width == width
        assert stats.compact_slots == eng.bucket(len(ids))
        dev_ids = device_plane.to_host(scan._idx)
        assert len(dev_ids) == eng.bucket(len(ids))
        np.testing.assert_array_equal(dev_ids[:len(ids)], ids)
    assert stats.compact_slots <= stats.compact_width


def test_device_engine_empty_inputs_delegate():
    """The engine entry handles zero-length sides (the device kernel
    itself is only entered with rows on both sides)."""
    from repro.core.engine_join import get_join_engine
    eng = get_join_engine("jax", device_resident=True)
    for how in HOWS:
        for bk, pk in ((np.empty(0, np.int64), np.array([1], np.int64)),
                       (np.array([1], np.int64), np.empty(0, np.int64)),
                       (np.empty(0, np.int64), np.empty(0, np.int64))):
            eb, ep = NumpyJoinEngine().join_indices(bk, pk, how)
            gb, gp = eng.join_indices(bk, pk, how)
            np.testing.assert_array_equal(np.asarray(gb), eb)
            np.testing.assert_array_equal(np.asarray(gp), ep)


small_keys = st.lists(st.integers(min_value=-12, max_value=12),
                      min_size=1, max_size=40)


@settings(max_examples=50, deadline=None)
@given(small_keys, small_keys, st.sampled_from(HOWS),
       st.booleans(), st.booleans())
def test_hypothesis_segjoin_device_vs_reference(a, b, how, use_bv,
                                               use_pv):
    bk, pk = np.array(a, np.int64), np.array(b, np.int64)
    bv = (np.arange(len(bk)) % 3 != 0) if use_bv else None
    pv = (np.arange(len(pk)) % 2 == 0) if use_pv else None
    _check_segjoin(bk, pk, how, bv, pv)


# --------------------------------------------------------------------------
# TPC-H: bit-exactness with the device plane forced on + round-trip cut
# --------------------------------------------------------------------------


def _device_cfg(strategy, backend, device="on"):
    return ExecConfig(
        strategy=make_strategy(strategy, backend=backend,
                               device_resident=(device == "on")),
        join_backend=backend, device=device)


@pytest.mark.parametrize("strategy", ["pred-trans",
                                      "pred-trans-adaptive"])
@pytest.mark.parametrize("qn", sorted(QUERIES))
def test_tpch_device_plane_jax_bit_exact(tpch_small, qn, strategy):
    ref, _ = Executor(tpch_small,
                      ExecConfig(late_materialize=False)).execute(
        build_query(qn, sf=0.01))
    res, stats = Executor(tpch_small,
                          _device_cfg(strategy, "jax")).execute(
        build_query(qn, sf=0.01))
    _assert_tables_exact(ref, res, (qn, strategy))
    assert stats.report()["device"]["h2d_syncs"] > 0


@pytest.mark.parametrize("qn", sorted(QUERIES))
def test_tpch_device_plane_pallas_interpret_bit_exact(tpch_tiny, qn):
    """The full device plane with the pallas bloom engine in interpret
    mode, on the tiny catalog (interpret kernels run at Python speed)."""
    ref, _ = Executor(tpch_tiny,
                      ExecConfig(late_materialize=False)).execute(
        build_query(qn, sf=0.002))
    res, _ = Executor(tpch_tiny,
                      _device_cfg("pred-trans", "pallas")).execute(
        build_query(qn, sf=0.002))
    _assert_tables_exact(ref, res, qn)


def test_device_plane_cuts_round_trips(tpch_small):
    """On the widest join graphs the fused plane must beat the legacy
    per-op path on host<->device round trips — counts, not clocks, so
    this is deterministic. Both modes are counted through
    `device_plane`, so the comparison is symmetric."""
    tot = {"on": 0, "off": 0}
    for qn in (5, 8, 9, 21):
        digests = {}
        for mode in ("on", "off"):
            res, stats = Executor(tpch_small,
                                  _device_cfg("pred-trans", "jax",
                                              mode)).execute(
                build_query(qn, sf=0.01))
            rep = stats.report()["device"]
            assert set(rep) == {"h2d_syncs", "h2d_bytes", "d2h_syncs",
                                "d2h_bytes", "round_trips",
                                "fused_calls", "compact_calls",
                                "compact_slots", "compact_width",
                                "probe_tiles", "probe_rows_walked",
                                "probe_width", "segjoin_calls",
                                "segjoin_narrow"}
            assert rep["compact_slots"] <= rep["compact_width"]
            assert rep["segjoin_narrow"] <= rep["segjoin_calls"]
            assert (rep["segjoin_calls"] > 0) == (mode == "on")
            assert rep["probe_rows_walked"] <= rep["probe_width"]
            tot[mode] += rep["round_trips"]
            digests[mode] = res
        _assert_tables_exact(digests["on"], digests["off"], qn)
    assert tot["on"] < tot["off"], tot


def test_device_knob_validation():
    with pytest.raises(ValueError):
        ExecConfig(device="maybe")


# --------------------------------------------------------------------------
# artifact cache: cost-to-rebuild weighted eviction
# --------------------------------------------------------------------------


def test_eviction_prefers_cheap_over_old():
    c = ArtifactCache(max_bytes=100, verify_on_hit=False)
    c.put(("bloom", 1), b"a", 40, cost_ns=1_000_000)    # dear, oldest
    c.put(("bloom", 2), b"b", 40, cost_ns=10)           # cheap
    c.put(("bloom", 3), b"c", 40, cost_ns=1_000_000)    # forces evict
    assert c.get(("bloom", 2)) is None                  # cheap went
    assert c.get(("bloom", 1)) == b"a"                  # old+dear stays
    assert c.get(("bloom", 3)) == b"c"


def test_eviction_unknown_cost_goes_before_known():
    c = ArtifactCache(max_bytes=100, verify_on_hit=False)
    c.put(("bloom", 1), b"a", 40, cost_ns=5)
    c.put(("bloom", 2), b"b", 40)                       # unknown cost
    c.put(("bloom", 3), b"c", 40, cost_ns=5)
    assert c.get(("bloom", 2)) is None
    assert c.get(("bloom", 1)) == b"a"
    assert c.get(("bloom", 3)) == b"c"


def test_eviction_cost_density_is_per_byte():
    """A dear-per-artifact but cheap-per-byte entry loses to a small
    entry of equal cost: eviction frees the most bytes per rebuild-ns."""
    c = ArtifactCache(max_bytes=100, verify_on_hit=False)
    c.put(("bloom", 1), b"a", 80, cost_ns=1000)         # density 12.5
    c.put(("bloom", 2), b"b", 10, cost_ns=1000)         # density 100
    c.put(("bloom", 3), b"c", 20, cost_ns=1000)         # forces evict
    assert c.get(("bloom", 1)) is None
    assert c.get(("bloom", 2)) == b"b"
    assert c.get(("bloom", 3)) == b"c"


def test_eviction_tie_keeps_lru_order():
    c = ArtifactCache(max_bytes=100, verify_on_hit=False)
    c.put(("bloom", 1), b"a", 40, cost_ns=7)
    c.put(("bloom", 2), b"b", 40, cost_ns=7)
    c.get(("bloom", 1))                                 # refresh 1
    c.put(("bloom", 3), b"c", 40, cost_ns=7)            # forces evict
    assert c.get(("bloom", 2)) is None                  # LRU on tie
    assert c.get(("bloom", 1)) == b"a"
    assert c.get(("bloom", 3)) == b"c"
