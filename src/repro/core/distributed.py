"""Distributed predicate transfer (paper §5 future work, built here).

Tables are row-partitioned across the `data` mesh axis. One transfer edge
runs as:

  1. each shard builds a *local* Bloom filter over its partition's keys
     (repro.core.bloom.build — same blocked filter as single-node);
  2. the shards combine filters with a **bitwise-OR all-reduce**
     (all_gather + local OR over the gathered filter copies — the filter
     is KBs–MBs, so the wire cost is O(filter) and independent of table
     size);
  3. every shard probes its local partition — no row ever crosses the
     interconnect.

The semi-join alternative (`distributed_semi_join`) must all-gather the
*key column itself* — O(rows) wire bytes. The roofline bench
(benchmarks/distributed_transfer.py) quantifies the gap; this asymmetry
is the paper's "succinct filter" insight mapped onto ICI collectives.

Everything here is shard_map-based and jit-compatible. Filter sizing and
host-side batching live in `repro.core.engine_bloom` (the engine's
`make_distributed_transfer` / `shard_keys` are the strategy-facing entry
points); this module owns the collectives.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import bloom, hashing


def _or_all_reduce(words: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Bitwise-OR all-reduce via all_gather + local OR (XLA has no OR
    collective; the gather payload is the KB-scale filter).

    Wire bytes per device: (p-1)·filter. Fine for small p / small
    filters; `_or_all_reduce_tree` scales as log2(p)·filter."""
    gathered = jax.lax.all_gather(words, axis_name)     # [shards, nb, 8]
    # lax.reduce with bitwise_or over the shard axis
    return jax.lax.reduce(gathered, np.uint32(0),
                          jnp.bitwise_or, dimensions=(0,))


def _or_all_reduce_tree(words: jnp.ndarray, axis_name: str,
                        axis_size: int) -> jnp.ndarray:
    """Recursive-doubling OR all-reduce: log2(p) collective_permute
    rounds of one filter each — the scalable path for p = 256+ shards
    (benchmarks/distributed_transfer.py quantifies the crossover)."""
    assert axis_size & (axis_size - 1) == 0, "power-of-two shards"
    out = words
    step = 1
    while step < axis_size:
        perm = [(i, i ^ step) for i in range(axis_size)]
        other = jax.lax.ppermute(out, axis_name, perm)
        out = out | other
        step <<= 1
    return out


def distributed_bloom_build(lo: jnp.ndarray, hi: jnp.ndarray,
                            mask: jnp.ndarray, nblocks: int,
                            axis_name: str, k: int = bloom.DEFAULT_K
                            ) -> jnp.ndarray:
    """Inside shard_map: local build + OR all-reduce => global filter."""
    local = bloom.build(lo, hi, mask, nblocks, k)
    return _or_all_reduce(local, axis_name)


def make_distributed_transfer(mesh: Mesh, nblocks: int,
                              k: int = bloom.DEFAULT_K, axis: str = "data",
                              tree_or: bool = False):
    """jit'd edge transfer over row-sharded tables.

    (build_lo, build_hi, build_mask) live on the building relation's
    shards; (probe_lo, probe_hi, probe_mask) on the probing relation's.
    Returns the probing relation's reduced mask, still sharded."""

    sharded = P(axis) if "pod" not in mesh.axis_names else P(("pod", axis))
    axes = axis if "pod" not in mesh.axis_names else ("pod", axis)

    def edge_multi(blo, bhi, bmask, plo, phi, pmask):
        words = bloom.build(blo, bhi, bmask, nblocks, k)
        groups = axes if isinstance(axes, tuple) else (axes,)
        for a in groups:
            if tree_or:
                words = _or_all_reduce_tree(words, a, mesh.shape[a])
            else:
                words = _or_all_reduce(words, a)
        hit = bloom.probe(words, plo, phi, k)
        return pmask & hit

    fn = jax.shard_map(
        edge_multi, mesh=mesh,
        in_specs=(sharded,) * 6,
        out_specs=sharded)
    return jax.jit(fn)


def distributed_semi_join(mesh: Mesh, axis: str = "data"):
    """Precise distributed semi-join baseline: all-gathers the build-side
    key column (O(rows) wire bytes vs the Bloom path's O(filter))."""

    def edge(bkeys, bmask, pkeys, pmask):
        keys = jax.lax.all_gather(bkeys, axis).reshape(-1)
        valid = jax.lax.all_gather(bmask, axis).reshape(-1)
        # membership via sort: replace invalid with a sentinel
        sentinel = jnp.int64(np.iinfo(np.int64).max) \
            if keys.dtype == jnp.int64 else jnp.iinfo(keys.dtype).max
        keys = jnp.where(valid, keys, sentinel)
        skeys = jnp.sort(keys)
        pos = jnp.clip(jnp.searchsorted(skeys, pkeys), 0, len(skeys) - 1)
        hit = skeys[pos] == pkeys
        return pmask & hit

    fn = jax.shard_map(edge, mesh=mesh,
                       in_specs=(P(axis),) * 4, out_specs=P(axis))
    return jax.jit(fn)


def shard_table_arrays(keys: np.ndarray, mesh: Mesh, axis: str = "data",
                       bucket: bool = False
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Host helper: split int64 keys into padded (lo, hi, mask) device
    arrays row-sharded over `axis`. With `bucket=True` the per-shard row
    count is rounded up to a power-of-two bucket (engine contract: the
    jit cache then holds O(log n) entries across table sizes)."""
    n_shards = mesh.shape[axis]
    n = len(keys)
    per = -(-n // n_shards)
    if bucket:
        per = bloom._bucket(per)
    pad = per * n_shards - n
    keys_p = np.concatenate([keys, np.zeros(pad, keys.dtype)])
    mask = np.concatenate([np.ones(n, bool), np.zeros(pad, bool)])
    lo, hi = hashing.key_halves(keys_p)
    sh = NamedSharding(mesh, P(axis))
    return (jax.device_put(jnp.asarray(lo), sh),
            jax.device_put(jnp.asarray(hi), sh),
            jax.device_put(jnp.asarray(mask), sh))
