"""Blocked (register-blocked) Bloom filter in JAX.

TPU adaptation of the paper's Bloom filters (DESIGN.md §3): one hash picks a
256-bit block (8 uint32 lanes == one VMEM word row); k bits are set/tested
*within* the block via double hashing. A probe costs one dynamic block load
plus vectorized bit math — no k dependent random accesses.

This module is the framework-level (pure jnp, jit-compatible) implementation
and is also the oracle for the Pallas kernels in `repro.kernels.bloom`.

Shapes are static: filters are sized by `blocks_for(n)` and key batches are
padded to power-of-two buckets by the engine layer — see
`repro.core.engine_bloom` (batched, backend-pluggable runtime wiring these
ops and the Pallas kernels into the transfer hot path) — so jit caches
stay at O(log n) entries.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing

BLOCK_BITS = 256          # bits per block
LANES = BLOCK_BITS // 32  # 8 uint32 lanes per block
DEFAULT_BITS_PER_KEY = 16
DEFAULT_K = 4


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BloomFilter:
    """words: uint32 [nblocks, LANES]. nblocks is a power of two."""
    words: jnp.ndarray
    k: int = DEFAULT_K

    @property
    def nblocks(self) -> int:
        return self.words.shape[0]

    @property
    def nbits(self) -> int:
        return self.nblocks * BLOCK_BITS

    def nbytes(self) -> int:
        return self.nblocks * LANES * 4

    def tree_flatten(self):
        return (self.words,), (self.k,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0])

    def fold_to(self, nblocks: int) -> "BloomFilter":
        """Shrink to a smaller power-of-two block count by OR-folding.

        Valid because the block index is the high bits of the hash:
        halving the block count drops the lowest block-index bit, i.e.
        blocks (2i, 2i+1) merge into block i."""
        assert nblocks <= self.nblocks and nblocks & (nblocks - 1) == 0
        w = self.words
        while w.shape[0] > nblocks:
            w = w.reshape(w.shape[0] // 2, 2, LANES)
            w = w[:, 0, :] | w[:, 1, :]
        return BloomFilter(w, self.k)

    def union(self, other: "BloomFilter") -> "BloomFilter":
        assert self.k == other.k
        n = min(self.nblocks, other.nblocks)
        a, b = self.fold_to(n), other.fold_to(n)
        return BloomFilter(a.words | b.words, self.k)


def blocks_for(n_keys: int, bits_per_key: int = DEFAULT_BITS_PER_KEY) -> int:
    """Power-of-two block count for ~n_keys insertions."""
    bits = max(int(n_keys) * bits_per_key, BLOCK_BITS)
    nblocks = max(1, int(2 ** np.ceil(np.log2(bits / BLOCK_BITS))))
    return nblocks


def _positions(h: jnp.ndarray, k: int) -> jnp.ndarray:
    """k in-block bit positions [n, k] via double hashing (odd stride)."""
    g1 = hashing.fmix32(h ^ hashing.GOLDEN)
    g2 = hashing.fmix32(h ^ jnp.uint32(0x7FEB352D)) | jnp.uint32(1)
    j = jnp.arange(k, dtype=jnp.uint32)
    return (g1[:, None] + j[None, :] * g2[:, None]) & jnp.uint32(
        BLOCK_BITS - 1)


def _block_index(h: jnp.ndarray, nblocks: int) -> jnp.ndarray:
    # use high bits for the block so they are independent of the low bits
    # used by double hashing inside the block
    return (h >> jnp.uint32(32 - int(np.log2(nblocks)))) if nblocks > 1 \
        else jnp.zeros_like(h)


@functools.partial(jax.jit, static_argnames=("nblocks", "k"))
def build(lo: jnp.ndarray, hi: jnp.ndarray, mask: jnp.ndarray,
          nblocks: int, k: int = DEFAULT_K) -> jnp.ndarray:
    """Build filter words from uint32 key halves; rows with mask=False are
    dropped (out-of-range scatter index -> mode='drop')."""
    h = hashing.hash64(lo, hi)
    blk = _block_index(h, nblocks).astype(jnp.int32)
    blk = jnp.where(mask, blk, jnp.int32(nblocks))  # dropped
    pos = _positions(h, k).astype(jnp.int32)        # [n, k]
    bits = jnp.zeros((nblocks, BLOCK_BITS), jnp.bool_)
    bits = bits.at[blk[:, None], pos].max(True, mode="drop")
    # pack bools -> uint32 lanes
    bits = bits.reshape(nblocks, LANES, 32).astype(jnp.uint32)
    shifts = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (bits * shifts[None, None, :]).sum(axis=-1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("k",))
def probe(words: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
          k: int = DEFAULT_K) -> jnp.ndarray:
    """Membership test -> bool [n]. False negatives impossible."""
    nblocks = words.shape[0]
    h = hashing.hash64(lo, hi)
    blk = _block_index(h, nblocks).astype(jnp.int32)
    pos = _positions(h, k).astype(jnp.int32)            # [n, k]
    rows = words[blk]                                    # [n, LANES] gather
    lane = pos >> 5
    bit = (pos & 31).astype(jnp.uint32)
    w = jnp.take_along_axis(rows, lane, axis=1)          # [n, k]
    hits = (w >> bit) & jnp.uint32(1)
    return jnp.all(hits == 1, axis=1)


@jax.jit
def hash_state(lo: jnp.ndarray, hi: jnp.ndarray) -> Tuple[jnp.ndarray,
                                                          jnp.ndarray,
                                                          jnp.ndarray]:
    """(h, g1, g2) device hash state from uint32 key halves — computed
    once per key column and reused by every `probe_rows` call
    (the device analogue of the host engine's lazy hash cache)."""
    h = hashing.hash64(lo, hi)
    g1 = hashing.fmix32(h ^ hashing.GOLDEN)
    g2 = hashing.fmix32(h ^ jnp.uint32(0x7FEB352D)) | jnp.uint32(1)
    return h, g1, g2


def probe_rows(words, h, g1, g2, k: int) -> jnp.ndarray:
    """`probe` from pre-hashed state (`hash_state`; no rehash per
    filter), bit-identical to it: one block-row gather per key, then
    the k in-block words. (A flat word gather would need the
    (nblocks, 8) -> flat relayout, which XLA's TPU compiler takes tens
    of seconds over at 2^15-2^17 blocks.)"""
    rows = words[_block_index(h, words.shape[0]).astype(jnp.int32)]
    out = jnp.ones(h.shape, jnp.bool_)
    for j in range(k):
        pos = (g1 + jnp.uint32(j) * g2) & jnp.uint32(BLOCK_BITS - 1)
        w = jnp.take_along_axis(
            rows, (pos >> jnp.uint32(5)).astype(jnp.int32)[:, None],
            axis=1)[:, 0]
        out &= ((w >> (pos & jnp.uint32(31))) & jnp.uint32(1)) == 1
    return out


@functools.partial(jax.jit, static_argnames=("nblocks", "k"))
def transfer(in_words: jnp.ndarray,
             in_lo: jnp.ndarray, in_hi: jnp.ndarray,
             out_lo: jnp.ndarray, out_hi: jnp.ndarray,
             mask: jnp.ndarray, nblocks: int, k: int = DEFAULT_K
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused filter transformation (paper §3.2): probe the incoming filter
    on the incoming join key; for passing rows insert the outgoing join key
    into a fresh outgoing filter. One scan, two filters.

    Returns (survivor_mask, out_words)."""
    ok = mask & probe(in_words, in_lo, in_hi, k=k)
    out_words = build(out_lo, out_hi, ok, nblocks, k=k)
    return ok, out_words


# -- host (numpy) mirror -----------------------------------------------------
#
# Bit-identical to the jnp implementation above (tests assert exact word
# equality). The relational engine's CPU wall-clock path uses this mirror;
# the jnp version is the framework/distributed path and the oracle for the
# Pallas TPU kernels. Rationale in DESIGN.md §7 (engine timing on CPU).


def _positions_np(h: np.ndarray, k: int) -> np.ndarray:
    with np.errstate(over="ignore"):
        g1 = hashing.fmix32_np(h ^ hashing.GOLDEN)
        g2 = hashing.fmix32_np(h ^ np.uint32(0x7FEB352D)) | np.uint32(1)
        j = np.arange(k, dtype=np.uint32)
        return (g1[:, None] + j[None, :] * g2[:, None]) & np.uint32(
            BLOCK_BITS - 1)


def _block_index_np(h: np.ndarray, nblocks: int) -> np.ndarray:
    if nblocks == 1:
        return np.zeros_like(h)
    return h >> np.uint32(32 - int(np.log2(nblocks)))


def build_np(lo: np.ndarray, hi: np.ndarray, mask: np.ndarray,
             nblocks: int, k: int = DEFAULT_K) -> np.ndarray:
    h = hashing.hash64_np(lo, hi)
    m = np.asarray(mask, bool)
    if not m.all():
        h = h[m]
    blk = _block_index_np(h, nblocks).astype(np.int64)
    pos = _positions_np(h, k).astype(np.int64)
    # flat bit index; constant-True fancy assignment needs no
    # read-modify-write, so duplicate indices are free
    fidx = blk[:, None] * BLOCK_BITS + pos
    bits = np.zeros(nblocks * BLOCK_BITS, bool)
    bits[fidx.ravel()] = True
    # little-endian packbits == the jnp shift-sum packing (bit j of word w
    # is flat bit 32*w + j); tests assert bit-exact equality
    return np.packbits(bits, bitorder="little").view(np.uint32).reshape(
        nblocks, LANES)


def probe_np(words: np.ndarray, lo: np.ndarray, hi: np.ndarray,
             k: int = DEFAULT_K) -> np.ndarray:
    nblocks = words.shape[0]
    h = hashing.hash64_np(lo, hi)
    blk = _block_index_np(h, nblocks).astype(np.int64)
    pos = _positions_np(h, k)
    flat = words.reshape(-1)
    out = np.ones(len(h), bool)
    base = blk * LANES
    for j in range(k):                     # k flat gathers, no [n,k] temp
        pj = pos[:, j]
        w = flat[base + (pj >> 5)]
        out &= (w >> (pj & np.uint32(31)) & np.uint32(1)) == 1
    return out


# -- min-max (zone) filters --------------------------------------------------
#
# Near-free complement to the Bloom filters (DESIGN.md §11): a transfer
# edge's build side publishes the [lo, hi] range of its *live, valid*
# keys alongside the Bloom words. The probing side can then
#
#   * short-circuit the whole edge when the ranges are provably
#     disjoint (every probe key misses — no hash, no probe);
#   * skip the range test when its own conservative range is contained
#     in the build range (the min-max filter provably passes every row);
#   * otherwise apply the O(1)-per-row comparison *before* the Bloom
#     probe, so out-of-range rows never reach the hash rounds.
#
# Ranges are only meaningful for order-preserving key encodings
# (single non-dictionary columns and the packed two-column path —
# `ops.stable_key_encoding`); the hash-combine fallback scrambles
# order, so the scheduler disables min-max there.


@dataclasses.dataclass(frozen=True)
class MinMaxFilter:
    """Closed key range [lo, hi] of a filter's inserted keys. An empty
    build side is encoded as (0, -1) (matches `Column.value_range`) and
    is disjoint from everything."""

    lo: int
    hi: int

    @property
    def empty(self) -> bool:
        return self.hi < self.lo

    def disjoint(self, lo: int, hi: int) -> bool:
        """No key in [lo, hi] can be in this filter."""
        return self.empty or hi < self.lo or self.hi < lo

    def contains(self, lo: int, hi: int) -> bool:
        """Every key in [lo, hi] passes this filter (non-filtering)."""
        return (not self.empty) and self.lo <= lo and hi <= self.hi

    def probe_np(self, keys: np.ndarray) -> np.ndarray:
        if self.empty:
            return np.zeros(len(keys), bool)
        return (keys >= self.lo) & (keys <= self.hi)


def key_range(keys: np.ndarray) -> Tuple[int, int]:
    """(min, max) of a key vector; empty -> (0, -1)."""
    if len(keys) == 0:
        return (0, -1)
    return int(keys.min()), int(keys.max())


# -- KMV distinct-count estimator --------------------------------------------
#
# The adaptive transfer scheduler (repro.core.transfer) estimates a
# build side's live distinct-key count from the hash state the Bloom
# build needs anyway (`EngineKeys.hga` — uniform uint32), so the
# estimate costs one partition pass over already-computed hashes and
# never an extra scan of the table. K-minimum-values: with the k-th
# smallest of n uniform hashes at position t in [0, 2^32), the distinct
# count is ≈ (k-1) · 2^32 / t (Bar-Yossef et al.; ±1/sqrt(k) relative
# error — k=256 gives ~6%, plenty for a skip/apply decision).

KMV_K = 256


def kmv_distinct(h: np.ndarray, k: int = KMV_K) -> int:
    """Distinct-count estimate from uint32 hash values (exact below
    ~4k rows). Duplicate keys put duplicate hashes among the minima, so
    the partition width grows (O(n) per round, bounded at 16k values
    examined) until it holds k *distinct* values; if heavy multiplicity
    exhausts the budget first, the estimate comes from however many
    distinct minima were found (same threshold semantics, wider error
    bars — fine for a skip/apply decision, where a low-cardinality
    build side reads sel ≈ 1 regardless). Never a full O(n log n) sort
    of the column."""
    n = len(h)
    if n == 0:
        return 0
    if n <= 4 * k:
        return len(np.unique(h))
    kk = k
    while True:
        kk = min(kk, n)
        uniq = np.unique(np.partition(h, kk - 1)[: kk] if kk < n
                         else h)
        if len(uniq) >= k or kk >= min(n, 16 * k):
            break
        kk *= 4
    kd = min(len(uniq), k)
    t = int(uniq[kd - 1])
    if kd < 2 or t == 0:
        return kd
    return max(kd, int((kd - 1) * (2.0 ** 32) / t))


# -- hash-once key cache -----------------------------------------------------
#
# Predicate transfer touches the same (vertex, key column) many times: a
# column is probed by several incoming filters and inserted into several
# outgoing filters across the forward and backward passes. The hash values
# and in-block bit positions depend only on the key, so we compute them
# once per column and reuse (the vectorized analogue of the paper's
# "transformation scans the join keys only once"; see EXPERIMENTS.md §Perf
# for the measured effect).


@dataclasses.dataclass
class HashedKeys:
    """Hash state per key: block hash + double-hash generators. In-block
    bit positions are derived lazily per probe round for the *surviving*
    subset only — avoids materializing [n, k] position arrays (§Perf DB
    iteration: −30% hashing traffic)."""
    h: np.ndarray        # uint32 [n]  (block hash)
    g1: np.ndarray       # uint32 [n]
    g2: np.ndarray       # uint32 [n]  (odd stride)
    k: int

    def __len__(self):
        return len(self.h)

    def pos_j(self, j: int, sel=None) -> np.ndarray:
        g1 = self.g1 if sel is None else self.g1[sel]
        g2 = self.g2 if sel is None else self.g2[sel]
        with np.errstate(over="ignore"):
            return (g1 + np.uint32(j) * g2) & np.uint32(BLOCK_BITS - 1)


def hash_keys(keys: np.ndarray, k: int = DEFAULT_K) -> HashedKeys:
    lo, hi = hashing.key_halves(np.asarray(keys))
    h = hashing.hash64_np(lo, hi)
    with np.errstate(over="ignore"):
        g1 = hashing.fmix32_np(h ^ hashing.GOLDEN)
        g2 = hashing.fmix32_np(h ^ np.uint32(0x7FEB352D)) | np.uint32(1)
    return HashedKeys(h, g1, g2, k)


def build_hashed(hk: HashedKeys, mask: np.ndarray | None, nblocks: int
                 ) -> np.ndarray:
    sel = None
    h = hk.h
    if mask is not None and not mask.all():
        sel = np.asarray(mask, bool)
        h = h[sel]
    blk = _block_index_np(h, nblocks).astype(np.int64) * BLOCK_BITS
    bits = np.zeros(nblocks * BLOCK_BITS, bool)
    for j in range(hk.k):
        bits[blk + hk.pos_j(j, sel).astype(np.int64)] = True
    return np.packbits(bits, bitorder="little").view(np.uint32).reshape(
        nblocks, LANES)


def probe_hashed(words: np.ndarray, hk: HashedKeys,
                 live: np.ndarray | None = None) -> np.ndarray:
    """Probe; if `live` (bool mask) is given, only live rows are tested
    (dead rows return False). Rows are dropped from the working set as
    soon as one hash misses — the vectorized version of per-row early
    exit; bit positions are derived lazily for survivors only."""
    n = len(hk)
    flat = words.reshape(-1)
    idx = np.flatnonzero(live) if live is not None else None
    h = hk.h if idx is None else hk.h[idx]
    nblocks = words.shape[0]
    base = _block_index_np(h, nblocks).astype(np.int64) * LANES
    alive = np.arange(n, dtype=np.int64) if idx is None else idx
    for j in range(hk.k):
        pj = hk.pos_j(j, alive)
        w = flat[base + (pj >> 5).astype(np.int64)]
        hit = (w >> (pj & np.uint32(31)) & np.uint32(1)) == 1
        if not hit.all():
            alive = alive[hit]
            base = base[hit]
        if len(alive) == 0:
            break
    out = np.zeros(n, bool)
    out[alive] = True
    return out


# -- host-facing convenience (used by the engine layer) ---------------------
#
# backend="numpy" (default) runs the host mirror; backend="jax" pads key
# batches to power-of-two buckets so the jit cache holds O(log n) entries.

def _bucket(n: int, floor: int = 64) -> int:
    """Power-of-two batch size (>= floor): keeps per-op jit/pallas
    caches at O(log n) entries. Canonical copy — the engine layer and
    the distributed shard helpers reuse it."""
    return max(floor, int(2 ** np.ceil(np.log2(max(n, 1)))))


def prefix_sum(x: jnp.ndarray, bits: int = 31) -> jnp.ndarray:
    """Inclusive int32 running sum of a non-negative 1-D array whose
    values fit in `bits` bits.

    Not `jnp.cumsum`: XLA's TPU compiler takes 8-35 s per shape for a
    cumsum at 2^18-2^21 elements. Here each 128-element segment is
    prefix-summed on the MXU — an exact int8 x int8 -> int32 product
    with an upper-triangular ones matrix, one product per 7-bit digit
    of the values — and only the segment totals (1/128 of the length)
    go through `jnp.cumsum`. Compiles in 1-3 s at 2^16-2^23."""
    n = x.shape[0]
    x = x.astype(jnp.int32)
    if n % 128:
        x = jnp.pad(x, (0, 128 - n % 128))
    seg = x.reshape(-1, 128)
    lanes = jnp.arange(128)
    tri = (lanes[:, None] <= lanes[None, :]).astype(jnp.int8)
    inner = jnp.zeros(seg.shape, jnp.int32)
    for d in range(0, bits, 7):
        digit = ((seg >> d) & 127).astype(jnp.int8)
        inner = inner + (jnp.dot(digit, tri,
                                 preferred_element_type=jnp.int32) << d)
    tot = inner[:, -1]
    run = inner + (jnp.cumsum(tot, dtype=jnp.int32) - tot)[:, None]
    return run.reshape(-1)[:n]


def flatnonzero(ok: jnp.ndarray, size: int) -> jnp.ndarray:
    """`jnp.nonzero(ok, size=size, fill_value=0)[0]` as int32, without a
    scatter: the j-th set position is the first index whose running
    count reaches j + 1 (a binary search over `prefix_sum`). XLA's TPU
    compiler spends 15-40 s per shape on the scatter form at 2^17-2^23
    rows."""
    run = prefix_sum(ok, bits=1)
    idx = jnp.searchsorted(run, jnp.arange(1, size + 1, dtype=jnp.int32),
                           side="left", method="scan")
    return jnp.where(idx < ok.shape[0], idx, 0).astype(jnp.int32)


def _pad(a: np.ndarray, n: int, fill=0) -> np.ndarray:
    if len(a) == n:
        return a
    out = np.full(n, fill, dtype=a.dtype)
    out[: len(a)] = a
    return out


def np_build(keys: np.ndarray, mask: np.ndarray | None = None,
             bits_per_key: int = DEFAULT_BITS_PER_KEY,
             k: int = DEFAULT_K, backend: str = "numpy") -> BloomFilter:
    keys = np.asarray(keys)
    n = int(mask.sum()) if mask is not None else len(keys)
    nblocks = blocks_for(max(n, 1), bits_per_key)
    if mask is None:
        mask = np.ones(len(keys), bool)
    if backend == "numpy":
        lo, hi = hashing.key_halves(keys)
        return BloomFilter(build_np(lo, hi, mask, nblocks, k), k)
    b = _bucket(len(keys))
    lo, hi = hashing.key_halves(_pad(keys, b))
    words = build(jnp.asarray(lo), jnp.asarray(hi),
                  jnp.asarray(_pad(mask, b, False)), nblocks, k)
    return BloomFilter(words, k)


def np_probe(filt: BloomFilter, keys: np.ndarray,
             backend: str = "numpy") -> np.ndarray:
    keys = np.asarray(keys)
    if backend == "numpy":
        lo, hi = hashing.key_halves(keys)
        return probe_np(np.asarray(filt.words), lo, hi, k=filt.k)
    b = _bucket(len(keys))
    lo, hi = hashing.key_halves(_pad(keys, b))
    out = np.asarray(probe(filt.words, jnp.asarray(lo), jnp.asarray(hi),
                           k=filt.k))
    return out[: len(keys)]
