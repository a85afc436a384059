"""The fused Bloom probe's walk over the live prefix, Pallas in interpret
mode against the plain references (`kernels/bloom/ref.py`, and the host
engine's `probe_packed_np` through a vertex scan):

* `_fused_pallas_count` / `_fused_pallas_gather` return the same
  survivor mask (False past `count`) and per-filter live counts as the
  reference probed over the whole width, for 1-4 filters and live
  counts at 0, 1, a multiple of the walk's tile, one past it, and the
  width;
* the walk's counters: `probe_rows_walked` is `probe_tiles` times the
  tile and at most `probe_width`, the probes' widths.

The tile is cut to two kernel tiles through `PROBE_TILE`, so that a
probe of a few thousand rows walks several tiles.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import device_plane, hashing
from repro.core import engine_bloom as eb
from repro.core.bloom import build_np
from repro.core.engine_bloom import get_engine
from repro.kernels.bloom import bloom as kb
from repro.kernels.bloom.ref import bloom_probe_ref

T = 2 * kb.TILE                 # the walk's tile in these tests
N = 4 * T                       # probe width: four tiles
NBLOCKS = (64, 8, 256, 16)      # one filter size per filter of a vertex


@pytest.fixture
def small_tile(monkeypatch):
    monkeypatch.setattr(kb, "PROBE_TILE", T)
    assert kb.walk_tile(N) == T


def _filters(rng, m):
    """m filters over keys drawn from [0, 3000), and m key columns of
    2N rows drawn from the same range (about half of them pass each
    filter)."""
    words, los, his = [], [], []
    for nb in NBLOCKS[:m]:
        lo, hi = hashing.key_halves(
            rng.integers(0, 3000, 1500).astype(np.int64))
        words.append(build_np(lo, hi, np.ones(len(lo), bool), nb))
        lo, hi = hashing.key_halves(
            rng.integers(0, 3000, 2 * N).astype(np.int64))
        los.append(lo)
        his.append(hi)
    return words, los, his


def _want(words, los, his, count, idx):
    """The reference: every filter probed over the whole width, ANDed,
    masked to the live prefix; the live count after each filter."""
    ok = np.arange(N) < count
    counts = []
    for w, lo, hi in zip(words, los, his):
        lo, hi = (lo[:N], hi[:N]) if idx is None else (lo[idx], hi[idx])
        ok = ok & np.asarray(bloom_probe_ref(jnp.asarray(w),
                                             jnp.asarray(lo),
                                             jnp.asarray(hi)))
        counts.append(int(ok.sum()))
    return ok, counts


@pytest.mark.parametrize("count", [0, 1, T, T + 1, N])
@pytest.mark.parametrize("m", [1, 2, 3, 4])
@pytest.mark.parametrize("variant", ["count", "gather"])
def test_walk_matches_reference(small_tile, variant, m, count):
    rng = np.random.default_rng(100 * m + count)
    words, los, his = _filters(rng, m)
    dev = lambda arrs: tuple(jnp.asarray(a) for a in arrs)  # noqa: E731
    if variant == "count":
        idx = None
        los, his = [a[:N] for a in los], [a[:N] for a in his]
        ok, counts = eb._fused_pallas_count(
            dev(words), dev(los), dev(his), count, k=4,
            tile=kb.walk_tile(N), interpret=True)
    else:
        # survivor ids into 2N-row columns, front-packed, zero-filled
        idx = np.zeros(N, np.int32)
        idx[:count] = np.sort(rng.choice(2 * N, count, replace=False))
        ok, counts = eb._fused_pallas_gather(
            dev(words), dev(los), dev(his), jnp.asarray(idx), count, k=4,
            tile=kb.walk_tile(N), interpret=True)
    want_ok, want_counts = _want(words, los, his, count, idx)
    ok = np.asarray(ok)
    assert ok.shape == (N,) and ok.dtype == np.bool_
    np.testing.assert_array_equal(ok, want_ok)
    assert not ok[count:].any()
    assert [int(c) for c in np.asarray(counts)] == want_counts


@pytest.mark.parametrize("live", [N, 3 * T - 5, T // 2])
@pytest.mark.parametrize("backend", ["pallas", "jax"])
def test_walk_counters(small_tile, backend, live):
    """Two probes of one vertex scan — over every row or over `live`
    survivors, then over what the first left — against the host
    engine: the same survivors and live counts, and counters that add
    up: the pallas engine walks ceil(live / t) tiles of t rows, t = T or
    the whole width where that is less, the jax engine one tile of the
    whole width."""
    rng = np.random.default_rng(live)
    keys = rng.integers(0, 3000, N).astype(np.int64)
    mask = np.zeros(N, bool)
    mask[rng.choice(N, live, replace=False)] = True
    host = get_engine("numpy")
    filters = [np.asarray(host.build_filter(host.keys(
        rng.integers(0, 3000, 2000).astype(np.int64))).words)
        for _ in range(3)]
    outs = {}
    for b in ("numpy", backend):
        eng = host if b == "numpy" else get_engine(b, device_resident=True)
        stats = device_plane.DeviceStats()
        with device_plane.track(stats):
            scan = eng.begin(mask)
            scan.probe([(filters[0], eng.keys(keys)),
                        (filters[1], eng.keys(keys))])
            after_first = int(scan.live)
            scan.probe([(filters[2], eng.keys(keys))])
        outs[b] = (np.flatnonzero(scan.mask), list(scan.live_after))
    np.testing.assert_array_equal(outs[backend][0], outs["numpy"][0])
    assert outs[backend][1] == outs["numpy"][1]
    widths = [eng.bucket(live), eng.bucket(after_first)]
    assert stats.probe_width == sum(widths)
    if backend == "pallas":
        walks = [(-(-n // min(T, w)), min(T, w))
                 for n, w in zip((live, after_first), widths)]
        assert stats.probe_tiles == sum(t for t, _ in walks)
        assert stats.probe_rows_walked == sum(t * r for t, r in walks)
        if min(widths) >= T:
            assert stats.probe_rows_walked == stats.probe_tiles * T
    else:
        assert stats.probe_tiles == 2
        assert stats.probe_rows_walked == stats.probe_width
    assert stats.probe_rows_walked <= stats.probe_width
    rep = stats.report()
    assert (rep["probe_tiles"], rep["probe_rows_walked"],
            rep["probe_width"]) == (stats.probe_tiles,
                                    stats.probe_rows_walked,
                                    stats.probe_width)
