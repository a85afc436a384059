"""Compile the main path's device code for a described TPU v5e.

No chip is attached: the TPU compiler is handed a `v5e:2x2` topology
description and compiles, at TPC-H SF-1 shapes, every Pallas kernel of
the transfer -> join path with `interpret=False`, plus the jitted pieces
of the device segment join; at SF-3 shapes, the fused probe (whose
compiled temp must stay under 1 GiB), the largest build and the segment
join. A kernel the chip's compiler would refuse
(block shapes, unsupported primitives, VMEM over budget) fails here. The
topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine_bloom as eb
from repro.kernels.bloom import bloom as kb
from repro.kernels.semijoin import ops as sj
from repro.kernels.semijoin import semijoin as ks

SF1_LINEITEM = 1 << 23          # 6.0M lineitem rows, bucketed
SF1_ORDERS = 1 << 21            # 1.5M orders rows, bucketed
SF3_LINEITEM = 1 << 25          # 18.0M lineitem rows, bucketed
SF3_ORDERS = 1 << 23            # 4.5M orders rows, bucketed
GiB = 1 << 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip could not be read back
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile()


@pytest.mark.parametrize("nblocks,n", [
    ((1 << 19,), SF1_LINEITEM),
    ((1 << 17, 1 << 10), SF1_LINEITEM),
    ((1 << 19, 1 << 15, 1 << 12), SF1_ORDERS),
])
def test_multi_probe_compiles(one_chip, nblocks, n):
    words = tuple(_spec(one_chip, (nb, 8)) for nb in nblocks)
    keys = tuple(_spec(one_chip, (n,)) for _ in nblocks)
    compiled = _compile(kb.multi_probe_pallas, words, keys, keys,
                        _spec(one_chip, (), jnp.int32), k=4,
                        interpret=False)
    assert "tpu_custom_call" in compiled.as_text()
    ok, counts = compiled.out_info
    assert (ok.shape, counts.shape) == ((n,), (len(nblocks),))


def test_probe_compiles(one_chip):
    key = _spec(one_chip, (SF1_LINEITEM,))
    compiled = _compile(
        lambda w, lo, hi: kb.probe_pallas(w, lo, hi, interpret=False),
        _spec(one_chip, (1 << 19, 8)), key, key)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("nblocks", [1, 1 << 10, 1 << 17, 1 << 19])
def test_build_compiles(one_chip, nblocks):
    key = _spec(one_chip, (SF1_LINEITEM,))
    compiled = _compile(
        lambda lo, hi, m: kb.build_pallas(lo, hi, m, nblocks,
                                          interpret=False),
        key, key, _spec(one_chip, (SF1_LINEITEM,), jnp.bool_))
    assert "tpu_custom_call" in compiled.as_text()


def test_build_refuses_filters_over_vmem_budget(one_chip):
    key = _spec(one_chip, (1 << 10,))
    too_big = 2 * kb.VMEM_FILTER_MAX // 32
    with pytest.raises(ValueError, match="VMEM budget"):
        _compile(lambda lo, hi, m: kb.build_pallas(lo, hi, m, too_big,
                                                   interpret=False),
                 key, key, _spec(one_chip, (1 << 10,), jnp.bool_))


def _fused_probe(one_chip, nblocks, n, nidx=None):
    """A fused probe program of the device-resident plane over n-row key
    columns, the count variant or (`nidx`) the gather variant over
    `nidx` survivor ids, compiled at the walk's tile."""
    words = tuple(_spec(one_chip, (nb, 8)) for nb in nblocks)
    keys = (_spec(one_chip, (n,)),) * len(nblocks)
    count = _spec(one_chip, (), jnp.int32)
    if nidx is None:
        return _compile(eb._fused_pallas_count, words, keys, keys, count,
                        k=4, tile=kb.walk_tile(n), interpret=False)
    return _compile(eb._fused_pallas_gather, words, keys, keys,
                    _spec(one_chip, (nidx,), jnp.int32), count, k=4,
                    tile=kb.walk_tile(nidx), interpret=False)


def test_engine_fused_probes_compile(one_chip):
    """The device-resident plane's per-vertex graphs around the kernel:
    the walk over the live prefix, returning the survivor mask and live
    counts (compaction is a program of its own)."""
    n = SF1_LINEITEM
    nblocks = (1 << 17, 1 << 13)
    for compiled, width in (
            (_fused_probe(one_chip, nblocks, n), n),
            (_fused_probe(one_chip, nblocks, n, 1 << 20), 1 << 20)):
        assert "tpu_custom_call" in compiled.as_text()
        ok, counts = compiled.out_info
        assert (ok.shape, ok.dtype) == ((width,), jnp.bool_)
        assert counts.shape == (len(nblocks),)


# the parent's compiled temp of the SF-1 probe over the whole bucket, in
# GiB, by filters: the walk must need no more
SF1_WHOLE_WIDTH_TEMP = {1: 0.5, 2: 0.75, 4: 4.5}


@pytest.mark.parametrize("m", sorted(SF1_WHOLE_WIDTH_TEMP))
def test_fused_probe_temp_at_sf1(one_chip, m):
    nblocks = (1 << 19, 1 << 17, 1 << 15, 1 << 13)[:m]
    temp = _fused_probe(one_chip, nblocks, SF1_LINEITEM) \
        .memory_analysis().temp_size_in_bytes
    assert temp <= SF1_WHOLE_WIDTH_TEMP[m] * GiB


@pytest.mark.parametrize("nidx", [None, 1 << 24])
def test_fused_probe_fits_at_sf3(one_chip, nidx):
    """Q9's and Q21's lineitem vertex at SF 3: 4 filters, the largest
    2^21 blocks, over the 2^25-row bucket or 2^24 survivors of it. Over
    the whole bucket the chip's compiler refused these programs (17 and
    18 GB of 15.75 GB of HBM); the walk keeps a tile's working set."""
    nblocks = (1 << 21, 1 << 19, 1 << 17, 1 << 13)
    compiled = _fused_probe(one_chip, nblocks, SF3_LINEITEM, nidx)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < GiB


@pytest.mark.parametrize("variant,n,size", [
    ("count", SF1_LINEITEM, 1 << 19),
    ("gather", 1 << 22, 1 << 16),
])
def test_fused_compactions_compile(one_chip, variant, n, size):
    """A fused probe's survivor compaction into the survivors' bucket:
    lineitem's 2^23-row mask into 2^19 slots, a 2^22-row survivor set
    into 2^16."""
    ok = _spec(one_chip, (n,), jnp.bool_)
    if variant == "count":
        compiled = _compile(eb._fused_pallas_count_compact, ok, size=size)
    else:
        compiled = _compile(eb._fused_pallas_gather_compact, ok,
                            _spec(one_chip, (n,), jnp.int32), size=size)
    assert compiled.out_info.shape == (size,)


def _compile_segjoin_counts(sharding, m, n):
    """The counts search over both build layouts: one key plane (lo,
    run, order) and two (lo, hi, run, order)."""
    count = _spec(sharding, (), jnp.int32)
    for planes in (3, 4):
        _compile(sj._segjoin_counts, _spec(sharding, (planes, m)),
                 _spec(sharding, (3, n)), count, count,
                 _spec(sharding, ()))


def test_segment_join_compiles(one_chip):
    """`segment_join_device`'s jitted pieces, orders build x lineitem
    probe."""
    i32 = jnp.int32
    n, m = SF1_LINEITEM, SF1_ORDERS
    count = _spec(one_chip, (), i32)
    col = _spec(one_chip, (n,), i32)
    _compile_segjoin_counts(one_chip, m, n)
    _compile(sj._segjoin_sel, col, count, want_zero=True)
    _compile(sj._segjoin_total, col)
    _compile(sj._segjoin_outcounts_left, col, count)
    _compile(sj._segjoin_emit, _spec(one_chip, (m,), i32), col, col, col,
             total_len=n, left=True)


def test_sf3_build_and_segment_join_compile(one_chip):
    """SF 3's largest Bloom build (2^25 keys into 2^21 blocks, the VMEM
    budget exactly) and its orders build x lineitem probe segment
    join."""
    key = _spec(one_chip, (SF3_LINEITEM,))
    compiled = _compile(
        lambda lo, hi, m: kb.build_pallas(lo, hi, m, 1 << 21,
                                          interpret=False),
        key, key, _spec(one_chip, (SF3_LINEITEM,), jnp.bool_))
    assert "tpu_custom_call" in compiled.as_text()
    i32 = jnp.int32
    n, m = SF3_LINEITEM, SF3_ORDERS
    col = _spec(one_chip, (n,), i32)
    _compile_segjoin_counts(one_chip, m, n)
    _compile(sj._segjoin_emit, _spec(one_chip, (m,), i32), col, col, col,
             total_len=n, left=False)


def test_semijoin_kernels_compile(one_chip):
    """The join map `PallasJoinEngine` builds and probes with the
    device plane off, at its largest device build."""
    cap = sj.capacity_for(1 << 21)
    keys = _spec(one_chip, (1 << 21,))
    compiled = _compile(
        lambda lo, hi, m: ks.build_rows_pallas(lo, hi, m, cap,
                                               interpret=False),
        keys, keys, _spec(one_chip, (1 << 21,), jnp.bool_))
    assert "tpu_custom_call" in compiled.as_text()
    table = _spec(one_chip, (cap,))
    probe = _spec(one_chip, (SF1_LINEITEM,))
    compiled = _compile(
        lambda a, b, c, d, lo, hi: ks.lookup_pallas(a, b, c, d, lo, hi,
                                                    interpret=False),
        table, table, table, table, probe, probe)
    assert "tpu_custom_call" in compiled.as_text()
