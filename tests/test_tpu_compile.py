"""Compile the main path's device code for a described TPU v5e.

No chip is attached: the TPU compiler is handed a `v5e:2x2` topology
description and compiles, at TPC-H SF-1 shapes, every Pallas kernel of
the transfer -> join path with `interpret=False`, plus the jitted pieces
of the device segment join. A kernel the chip's compiler would refuse
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
                        k=4, interpret=False)
    assert "tpu_custom_call" in compiled.as_text()


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


def test_engine_fused_probes_compile(one_chip):
    """The device-resident plane's per-vertex graphs around the kernel:
    cumulative masks and live counts, returning the last survivor mask
    (compaction is a program of its own)."""
    n = SF1_LINEITEM
    words = (_spec(one_chip, (1 << 17, 8)), _spec(one_chip, (1 << 13, 8)))
    keys = (_spec(one_chip, (n,)),) * 2
    count = _spec(one_chip, (), jnp.int32)
    for compiled, width in (
            (_compile(eb._fused_pallas_count, words, keys, keys, count,
                      k=4, interpret=False), n),
            (_compile(eb._fused_pallas_gather, words, keys, keys,
                      _spec(one_chip, (1 << 20,), jnp.int32), count, k=4,
                      interpret=False), 1 << 20)):
        assert "tpu_custom_call" in compiled.as_text()
        ok, counts = compiled.out_info
        assert (ok.shape, ok.dtype) == ((width,), jnp.bool_)
        assert counts.shape == (len(words),)


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


def test_segment_join_compiles(one_chip):
    """`segment_join_device`'s jitted pieces, orders build x lineitem
    probe."""
    i32 = jnp.int32
    n, m = SF1_LINEITEM, SF1_ORDERS
    count = _spec(one_chip, (), i32)
    col = _spec(one_chip, (n,), i32)
    _compile(sj._segjoin_counts, _spec(one_chip, (4, m)),
             _spec(one_chip, (3, n)), count)
    _compile(sj._segjoin_sel, col, count, want_zero=True)
    _compile(sj._segjoin_total, col)
    _compile(sj._segjoin_outcounts_left, col, count)
    _compile(sj._segjoin_emit, _spec(one_chip, (m,), i32), col, col, col,
             total_len=n, left=True)


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
