"""Pallas kernels vs pure-jnp oracles (interpret mode), with shape/dtype
sweeps per the kernel-testing convention."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bloom as core_bloom, hashing
from repro.kernels.bloom import bloom as kb
from repro.kernels.bloom import bloom_build, bloom_probe, bloom_transfer
from repro.kernels.semijoin import semi_mask
from repro.kernels.semijoin.ref import semi_mask_ref


@pytest.mark.parametrize("nblocks", [1, 8, 256])
@pytest.mark.parametrize("n", [1024, 4096])
def test_bloom_build_probe_vs_oracle(rng, nblocks, n):
    keys = rng.integers(-2**62, 2**62, n).astype(np.int64)
    mask = rng.random(n) < 0.7
    lo, hi = hashing.key_halves(keys)
    lo, hi, m = jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(mask)
    ref_w = core_bloom.build(lo, hi, m, nblocks)
    w = kb.build_pallas(lo, hi, m, nblocks)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(ref_w))
    p = kb.probe_pallas(w, lo, hi)
    np.testing.assert_array_equal(
        np.asarray(p), np.asarray(core_bloom.probe(ref_w, lo, hi)))


@pytest.mark.parametrize("nblocks", [8, 128])
def test_bloom_transfer_fused_vs_oracle(rng, nblocks):
    n = 2048
    keys = rng.integers(0, 10**9, n).astype(np.int64)
    out_keys = rng.integers(0, 10**9, n).astype(np.int64)
    mask = rng.random(n) < 0.8
    lo, hi = map(jnp.asarray, hashing.key_halves(keys))
    olo, ohi = map(jnp.asarray, hashing.key_halves(out_keys))
    m = jnp.asarray(mask)
    in_w = core_bloom.build(lo, hi, m, nblocks)
    ok_ref, ow_ref = core_bloom.transfer(in_w, lo, hi, olo, ohi, m, nblocks)
    ok, ow = kb.transfer_pallas(in_w, lo, hi, olo, ohi, m, nblocks)
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(ok_ref))
    np.testing.assert_array_equal(np.asarray(ow), np.asarray(ow_ref))


def test_bloom_ops_wrappers_non_tile_aligned(rng):
    keys = rng.integers(0, 10**7, 5003).astype(np.int64)  # not % TILE
    w = bloom_build(keys)
    assert bloom_probe(w, keys).all()
    ok, ow = bloom_transfer(w, keys, keys * 7 + 1)
    assert ok.all()
    hit = bloom_probe(ow, keys * 7 + 1)
    assert hit.all()


@pytest.mark.parametrize("nb,npr", [(1, 64), (100, 3000), (2000, 5000),
                                    (5000, 100)])
def test_semijoin_vs_oracle(rng, nb, npr):
    build = rng.integers(-10**12, 10**12, nb).astype(np.int64)
    probe = np.concatenate([
        build[rng.integers(0, nb, npr // 2)],
        rng.integers(2 * 10**12, 3 * 10**12, npr - npr // 2)
        .astype(np.int64)])
    bm = rng.random(nb) < 0.8
    got = semi_mask(probe, build, bm)
    np.testing.assert_array_equal(got, semi_mask_ref(probe, build, bm))


def test_semijoin_duplicates_and_empty(rng):
    build = np.repeat(rng.integers(0, 50, 100).astype(np.int64), 3)
    probe = np.arange(-10, 120, dtype=np.int64)
    got = semi_mask(probe, build)
    np.testing.assert_array_equal(got, semi_mask_ref(probe, build))
    # all-masked build => nothing matches
    got = semi_mask(probe, build, np.zeros(len(build), bool))
    assert not got.any()


@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False),
                                           ("gpu", None)])
def test_resolve_interpret_by_platform(monkeypatch, platform, want):
    """Interpret mode defaults on for cpu only, off for tpu, and any
    other platform raises; an explicit flag always wins."""
    import jax

    from repro.kernels import resolve_interpret
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if want is None:
        with pytest.raises(RuntimeError, match=platform):
            resolve_interpret()
    else:
        assert resolve_interpret() is want
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
