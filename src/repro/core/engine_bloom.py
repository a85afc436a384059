"""Batched Bloom transfer engine: the hot path between the transfer
strategies and the filter kernels (DESIGN.md §7).

`repro.core.transfer.PredTrans` describes *what* flows along the transfer
graph; this module decides *how* each vertex's filter work is executed:

* **hash once, lazily** — `BloomEngine.keys` wraps a key column in
  `EngineKeys`; the full column's hash state materializes at most once
  per (vertex, column) — and only when a mostly-alive row set needs it,
  a survivor subset that earlier filters already shrank hashes just its
  own rows (the vectorized form of the paper's "transformation scans
  the join keys only once", §3.2, minus the rows that never survive to
  be scanned);
* **fused multi-filter probe** — all filters incoming at a vertex are
  packed into one concatenated word array with per-filter block offsets
  (`PackedFilters`) and applied in the given (LIP, most-selective-first)
  order over a single shrinking survivor set: rows leave the working set
  the moment one hash round of one filter misses, and the vertex's
  validity mask is materialized once, not once per edge;
* **one scan probe→build** — a `VertexScan` carries the survivor set
  from the probe half to the build half, so emitting each outgoing
  filter is a gather over survivors, never a rescan of the table;
* **compacted device scans** — the device backends keep a re-bucketed
  survivor-id array between probes (later filters probe ~survivors,
  not the padded column), hash each column on device once
  (`bloom.hash_state` + `probe_rows`), and off-TPU route builds
  through the bit-identical host mirror and compaction through host
  flatnonzero (XLA:CPU serializes the build scatter and scans for
  sized-nonzero; DESIGN.md §7);
* **bucketed batches** — key batches are padded to power-of-two buckets
  (`TILE`-aligned for Pallas) so the jit / pallas_call caches hold
  O(log n) entries per (op, nblocks), fulfilling the shape contract in
  `repro.core.bloom`'s docstring.

Three backends with bit-identical filter semantics (`tests/
test_engine_bloom.py` asserts word-level equality against the
`bloom.build_np` / `probe_np` oracle):

* ``numpy``  — host mirror; the CPU wall-clock path (DESIGN.md §7);
* ``jax``    — jit'd `repro.core.bloom` ops; the distributed path;
* ``pallas`` — `repro.kernels.bloom` TPU kernels (interpret mode on CPU).
"""
from __future__ import annotations

import dataclasses
import threading
import functools
import sys
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import bloom, device_plane, faultinject, hashing
from repro.core.bloom import (
    BLOCK_BITS, DEFAULT_BITS_PER_KEY, DEFAULT_K, LANES, BloomFilter,
    _bucket, _pad, blocks_for,
)

_LITTLE_ENDIAN = sys.byteorder == "little"

BACKENDS = ("numpy", "jax", "pallas")


# --------------------------------------------------------------------------
# key hash state
# --------------------------------------------------------------------------


@dataclasses.dataclass
class EngineKeys:
    """Per-column hash state, computed once and reused across all edges
    and passes.

    Host backend keeps the raw int64 keys and hashes *lazily*: the full
    column is hashed (and cached) only when a mostly-alive row set needs
    it; a shrunken survivor set is hashed directly from the raw keys —
    rows that an earlier filter already rejected are never hashed at
    all. Hash state is uint32 block hash + double-hash generators
    (4-byte probe-round traffic; int64 state measured ~1.5x slower on
    the Q5 hot path). Device backends keep the raw uint32 key halves and
    rehash on device; padded device copies are cached per bucket size."""

    n: int
    lo: Optional[np.ndarray] = None   # uint32 [n] (device backends)
    hi: Optional[np.ndarray] = None   # uint32 [n] (device backends)
    h: Optional[np.ndarray] = None    # uint32 [n] block hash (host)
    g1: Optional[np.ndarray] = None   # uint32 [n] (host)
    g2: Optional[np.ndarray] = None   # uint32 [n] (odd; host)
    raw: Optional[np.ndarray] = None  # int64 [n] (host, lazy source)
    _dev: Dict[int, Tuple] = dataclasses.field(default_factory=dict)
    _devh: Dict[int, Tuple] = dataclasses.field(default_factory=dict)

    def __len__(self):
        return self.n

    def _hash_subset(self, alive: np.ndarray) -> Tuple:
        if self.raw is not None:
            return _hash_host(self.raw[alive])
        return _hash_host_halves(self.lo[alive], self.hi[alive])

    def hga(self, alive: Optional[np.ndarray] = None) -> Tuple:
        """(h, g1, g2) over `alive` rows (None = every row). The full
        hash is computed once and cached; survivor subsets under half
        the column hash just their own rows (works from `raw` int64
        keys or from the device backends' uint32 halves — bit-identical
        either way)."""
        if self.h is None:
            with device_plane.span("transfer.hash"):
                if alive is not None and alive.size * 2 < self.n:
                    return self._hash_subset(alive)
                if self.raw is not None:
                    self.h, self.g1, self.g2 = _hash_host(self.raw)
                else:
                    self.h, self.g1, self.g2 = _hash_host_halves(self.lo,
                                                                 self.hi)
        if alive is None:
            return self.h, self.g1, self.g2
        return (self.h.take(alive), self.g1.take(alive),
                self.g2.take(alive))

    def dev(self, bucket: int):
        """Padded (lo, hi) device arrays, cached per power-of-two bucket."""
        hit = self._dev.get(bucket)
        if hit is None:
            from repro.core import device_plane as _dp
            hit = (_dp.to_device(_pad(self.lo, bucket)),
                   _dp.to_device(_pad(self.hi, bucket)))
            self._dev[bucket] = hit
        return hit

    def dev_hashed(self, bucket: int):
        """Padded (h, g1, g2) device hash state, computed once per
        bucket and reused by every probe (hash once, also on device)."""
        hit = self._devh.get(bucket)
        if hit is None:
            lo, hi = self.dev(bucket)
            hit = bloom.hash_state(lo, hi)
            self._devh[bucket] = hit
        return hit


def _hash_host(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                          np.ndarray]:
    """(h, g1, g2) uint32 hash state from int64 keys — the host mirror's
    hash pipeline (strided key halves, fused murmur finalizers)."""
    if not keys.flags.c_contiguous:
        keys = np.ascontiguousarray(keys)
    # strided views of the int64 words: same bits as hashing.key_halves,
    # one pass instead of mask+shift+cast
    v32 = keys.view(np.uint32)
    lo_s, hi_s = v32[0::2], v32[1::2]
    if not _LITTLE_ENDIAN:
        lo_s, hi_s = hi_s, lo_s
    return _hash_host_halves(lo_s, hi_s)


def _hash_host_halves(lo_s: np.ndarray, hi_s: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Hash pipeline from uint32 halves. `lo_s`/`hi_s` may be strided
    views — never mutated in place."""
    tmp = np.empty(len(lo_s), np.uint32)
    # .copy() (never ascontiguousarray: a 1-row strided view IS
    # contiguous and would alias the table column) — _fmix_into
    # mutates its argument
    with np.errstate(over="ignore"):
        if hi_s.any():
            # h = fmix32(lo ^ fmix32(hi))
            h = _fmix_into(hi_s.copy(), tmp)
            np.bitwise_xor(h, lo_s, out=h)
            _fmix_into(h, tmp)
        else:
            # fmix32(0) == 0, so 32-bit keys (every TPC-H key)
            # skip the hi mix: h = fmix32(lo)
            h = _fmix_into(lo_s.copy(), tmp)
        g1 = _fmix_into(h ^ hashing.GOLDEN, tmp)
        g2 = _fmix_into(h ^ np.uint32(0x7FEB352D), tmp)
        np.bitwise_or(g2, np.uint32(1), out=g2)
    return h, g1, g2


def _fmix_into(h: np.ndarray, tmp: np.ndarray) -> np.ndarray:
    """murmur3 finalizer, in place on `h` (owned uint32 scratch `tmp` of
    the same shape). Identical op sequence to `hashing.fmix32_np` —
    bit-exact, two live arrays instead of per-op temporaries."""
    np.right_shift(h, 16, out=tmp)
    np.bitwise_xor(h, tmp, out=h)
    np.multiply(h, np.uint32(0x85EBCA6B), out=h)
    np.right_shift(h, 13, out=tmp)
    np.bitwise_xor(h, tmp, out=h)
    np.multiply(h, np.uint32(0xC2B2AE35), out=h)
    np.right_shift(h, 16, out=tmp)
    np.bitwise_xor(h, tmp, out=h)
    return h


# --------------------------------------------------------------------------
# packed incoming filters (numpy fused probe)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class PackedFilters:
    """Incoming filters of one vertex, concatenated for a single fused
    probe: `words` stacks every filter's blocks, `offsets[f]` is filter
    f's first block in the stack, `log2nb[f]` its own block-count (each
    filter keeps its native size — no folding, so probing the pack is
    bit-identical to probing the filters one by one)."""

    words: np.ndarray                 # uint32 [sum(nblocks_f), LANES]
    offsets: np.ndarray               # int64 [m]
    log2nb: Tuple[int, ...]
    k: int


def pack_filters(filters: Sequence[np.ndarray], k: int) -> PackedFilters:
    log2nb = tuple(int(np.log2(w.shape[0])) for w in filters)
    if len(filters) == 1:
        words = np.ascontiguousarray(filters[0])
        offsets = np.zeros(1, np.int64)
    else:
        words = np.concatenate([np.asarray(w) for w in filters], axis=0)
        offsets = np.cumsum([0] + [w.shape[0] for w in filters[:-1]],
                            dtype=np.int64)
    return PackedFilters(words, offsets, log2nb, k)


def probe_packed_np(packed: PackedFilters, keys: Sequence[EngineKeys],
                    alive: Optional[np.ndarray], n_rows: int,
                    live_after: Optional[list] = None
                    ) -> Tuple[Optional[np.ndarray], int]:
    """Apply every packed filter, in order, to the `alive` row-index set
    (`alive=None` means every row — the common first-pass case, probed
    without materializing an index array or gathering hash state).

    Returns (surviving indices or None if all survived, rows actually
    probed). Survivors-only early exit at two levels: rows are dropped
    after the first missing hash round, and later filters see only
    earlier survivors. When `live_after` is given, the live count after
    each filter is appended to it (the adaptive scheduler's
    estimated-vs-actual selectivity feedback)."""
    flat = packed.words.reshape(-1)
    rows_probed = 0
    _u5, _u31, _upos = np.uint32(5), np.uint32(31), np.uint32(
        BLOCK_BITS - 1)
    for f in range(len(packed.offsets)):
        if alive is not None and alive.size == 0:
            if live_after is not None:
                live_after.append(0)
            continue
        m = n_rows if alive is None else int(alive.size)
        rows_probed += m
        l2 = packed.log2nb[f]
        h, g1, g2 = keys[f].hga(alive)
        off = int(packed.offsets[f])
        # uint32 word indices when the packed stack is small enough —
        # halves the index-arithmetic memory traffic on the hot round
        small = (off + (1 << l2)) * LANES < 2**31
        idt = np.uint32 if small else np.int64
        if l2:
            base = h >> np.uint32(32 - l2)          # fresh array, owned
            if not small:
                base = base.astype(np.int64)
            if off:
                base += idt(off)
            base *= idt(LANES)
        else:
            base = np.full(m, off * LANES, idt)
        cur = alive
        with np.errstate(over="ignore"):
            for j in range(packed.k):
                pos = (g1 & _upos) if j == 0 else \
                    ((g1 + np.uint32(j) * g2) & _upos)
                w = flat[base + (pos >> _u5)]
                hit = ((w >> (pos & _u31)) & np.uint32(1)) == 1
                if not hit.all():
                    # narrow by gathering survivors (reads ~survivors,
                    # not three full boolean passes)
                    sel = np.flatnonzero(hit)
                    cur = sel if cur is None else cur.take(sel)
                    base = base.take(sel)
                    g1 = g1.take(sel)
                    g2 = g2.take(sel)
                    if sel.size == 0:
                        break
        alive = cur
        if live_after is not None:
            live_after.append(n_rows if alive is None
                              else int(alive.size))
    return alive, rows_probed


def build_alive_np(ek: EngineKeys, alive: Optional[np.ndarray],
                   nblocks: int, k: int) -> np.ndarray:
    """Build filter words from the survivor index set (`alive=None` means
    every row). Bit-identical to `bloom.build_np` over the same rows."""
    h, g1, g2 = ek.hga(alive)
    l2 = int(np.log2(nblocks))
    if l2:
        blk = (h >> np.uint32(32 - l2)).astype(np.int64) * BLOCK_BITS
    else:
        blk = np.int64(0)
    bits = np.zeros(nblocks * BLOCK_BITS, bool)
    with np.errstate(over="ignore"):
        for j in range(k):
            pos = (g1 + np.uint32(j) * g2) & np.uint32(BLOCK_BITS - 1)
            bits[blk + pos] = True
    return np.packbits(bits, bitorder="little").view(np.uint32).reshape(
        nblocks, LANES)


# --------------------------------------------------------------------------
# device-scan jit helpers (bucketed shapes => O(log n) cache entries; the
# live-row count is a traced scalar so shrinking survivor counts never
# retrace)
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k",))
def _probe_hashed_count(words, h, g1, g2, count, k):
    ok = bloom.probe_rows(words, h, g1, g2, k)
    return ok & (jnp.arange(ok.shape[0]) < count)


@functools.partial(jax.jit, static_argnames=("k",))
def _probe_hashed_gather(words, h, g1, g2, idx, count, k):
    ok = bloom.probe_rows(words, h[idx], g1[idx], g2[idx], k)
    return ok & (jnp.arange(idx.shape[0]) < count)


@functools.partial(jax.jit, static_argnames=("nblocks", "k"))
def _build_count(lo, hi, count, nblocks, k):
    mask = jnp.arange(lo.shape[0]) < count
    return bloom.build(lo, hi, mask, nblocks, k=k)


@functools.partial(jax.jit, static_argnames=("nblocks", "k"))
def _build_gather(lo, hi, idx, count, nblocks, k):
    mask = jnp.arange(idx.shape[0]) < count
    return bloom.build(lo[idx], hi[idx], mask, nblocks, k=k)


@functools.partial(jax.jit, static_argnames=("nblocks", "k"))
def _build_count_valid(lo, hi, valid, count, nblocks, k):
    mask = (jnp.arange(lo.shape[0]) < count) & valid
    return bloom.build(lo, hi, mask, nblocks, k=k)


@functools.partial(jax.jit, static_argnames=("nblocks", "k"))
def _build_gather_valid(lo, hi, idx, valid, count, nblocks, k):
    mask = (jnp.arange(idx.shape[0]) < count) & valid[idx]
    return bloom.build(lo[idx], hi[idx], mask, nblocks, k=k)


@jax.jit
def _gather2(lo, hi, idx):
    return lo[idx], hi[idx]


@jax.jit
def _mask_count(ok, count):
    return ok & (jnp.arange(ok.shape[0]) < count)


@functools.partial(jax.jit, static_argnames=("size",))
def _iota_mask(size, count):
    return jnp.arange(size) < count


@functools.partial(jax.jit, static_argnames=("size",))
def _nonzero_idx(ok, size):
    return bloom.flatnonzero(ok, size)


@functools.partial(jax.jit, static_argnames=("size",))
def _nonzero_gather(ok, idx, size):
    return idx[bloom.flatnonzero(ok, size)]


def _compact(ok, idx, bucket: int):
    """New survivor-id array (original row ids) from a probe mask."""
    if idx is None:
        return _nonzero_idx(ok, bucket)
    return _nonzero_gather(ok, idx, bucket)


# --------------------------------------------------------------------------
# fused device probe + range-cut + min-max (the device-resident data plane,
# DESIGN.md §15): every incoming filter of a vertex is applied in one jit
# graph that returns the survivor mask and per-filter live counts, so the
# host syncs exactly one small counts vector per vertex instead of one mask
# per filter; the survivors are then compacted into their own bucket
# --------------------------------------------------------------------------


_SIGN = np.uint32(0x80000000)
_U32MAX = np.uint32(0xFFFFFFFF)


def _fused_and(words, hs, g1s, g2s, ok, k):
    """Traced fused-probe core: AND every incoming filter into `ok`,
    appending the live count after each filter. Same hash rounds as
    `probe_packed_np` — bit-identical survivors."""
    counts = []
    for f, w in enumerate(words):
        ok = ok & bloom.probe_rows(w, hs[f], g1s[f], g2s[f], k)
        counts.append(jnp.sum(ok, dtype=jnp.int32))
    return ok, jnp.stack(counts)


@functools.partial(jax.jit, static_argnames=("k",))
def _fused_probe_count(words, hs, g1s, g2s, count, k):
    n = hs[0].shape[0]
    ok = jnp.arange(n, dtype=jnp.int32) < count
    return _fused_and(words, hs, g1s, g2s, ok, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _fused_probe_gather(words, hs, g1s, g2s, idx, count, k):
    n = idx.shape[0]
    ok = jnp.arange(n, dtype=jnp.int32) < count
    hg = tuple(h[idx] for h in hs)
    g1g = tuple(g[idx] for g in g1s)
    g2g = tuple(g[idx] for g in g2s)
    return _fused_and(words, hg, g1g, g2g, ok, k)


@functools.partial(jax.jit, static_argnames=("k", "tile", "interpret"))
def _fused_pallas_count(words, los, his, count, k, tile, interpret):
    from repro.kernels.bloom import bloom as _k
    return _k.multi_probe_pallas(words, los, his, count, k=k, tile=tile,
                                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k", "tile", "interpret"))
def _fused_pallas_gather(words, los, his, idx, count, k, tile, interpret):
    from repro.kernels.bloom import bloom as _k
    return _k.multi_probe_pallas(words, los, his, count, idx, k=k,
                                 tile=tile, interpret=interpret)


# `_compact` under the names of the Pallas probe programs it completes, so
# a trace reader that selects `jit__fused_pallas_count*` /
# `jit__fused_pallas_gather*` times each probe with its compaction


@functools.partial(jax.jit, static_argnames=("size",))
def _fused_pallas_count_compact(ok, size):
    return bloom.flatnonzero(ok, size)


@functools.partial(jax.jit, static_argnames=("size",))
def _fused_pallas_gather_compact(ok, idx, size):
    return idx[bloom.flatnonzero(ok, size)]


def _bound_halves(v) -> Tuple[np.uint32, np.uint32, np.uint32]:
    """(lo_half, hi_half, hi_half with sign bit flipped) of an int64
    bound — the device compares signed int64 keys as (hi ^ sign, lo)
    unsigned lexicographic pairs."""
    u = int(v) & 0xFFFFFFFFFFFFFFFF
    lo = np.uint32(u & 0xFFFFFFFF)
    hi = np.uint32(u >> 32)
    return lo, hi, np.uint32(int(hi) ^ 0x80000000)


def _val_from_halves(hi_flipped: int, lo: int) -> int:
    """Inverse of `_bound_halves`: signed int64 from the device's
    (sign-flipped hi, lo) uint32 pair."""
    u = ((int(hi_flipped) ^ 0x80000000) << 32) | int(lo)
    return u - (1 << 64) if u >= (1 << 63) else u


def _range_keep(lo_col, hi_col, blo_lo, blo_hi, bhi_lo, bhi_hi):
    ah = hi_col ^ _SIGN
    return (((ah > blo_hi) | ((ah == blo_hi) & (lo_col >= blo_lo)))
            & ((ah < bhi_hi) | ((ah == bhi_hi) & (lo_col <= bhi_lo))))


@jax.jit
def _range_cut_count(lo_col, hi_col, count, blo_lo, blo_hi, bhi_lo,
                     bhi_hi):
    n = lo_col.shape[0]
    ok = (_range_keep(lo_col, hi_col, blo_lo, blo_hi, bhi_lo, bhi_hi)
          & (jnp.arange(n, dtype=jnp.int32) < count))
    return bloom.flatnonzero(ok, n), jnp.sum(ok, dtype=jnp.int32)


@jax.jit
def _range_cut_gather(lo_col, hi_col, idx, count, blo_lo, blo_hi,
                      bhi_lo, bhi_hi):
    n = idx.shape[0]
    ok = (_range_keep(lo_col[idx], hi_col[idx], blo_lo, blo_hi, bhi_lo,
                      bhi_hi)
          & (jnp.arange(n, dtype=jnp.int32) < count))
    return idx[bloom.flatnonzero(ok, n)], jnp.sum(ok, dtype=jnp.int32)


def _minmax_live(lo_col, hi_col, live):
    """Lexicographic (hi ^ sign, lo) min/max over live rows — the signed
    int64 key range as four uint32 scalars (one 16-byte sync)."""
    ah = hi_col ^ _SIGN
    hi_min = jnp.min(jnp.where(live, ah, _U32MAX))
    lo_min = jnp.min(jnp.where(live & (ah == hi_min), lo_col, _U32MAX))
    hi_max = jnp.max(jnp.where(live, ah, jnp.uint32(0)))
    lo_max = jnp.max(jnp.where(live & (ah == hi_max), lo_col,
                               jnp.uint32(0)))
    return jnp.stack([hi_min, lo_min, hi_max, lo_max])


@jax.jit
def _minmax_count(lo_col, hi_col, count):
    live = jnp.arange(lo_col.shape[0], dtype=jnp.int32) < count
    return _minmax_live(lo_col, hi_col, live)


@jax.jit
def _minmax_count_valid(lo_col, hi_col, count, valid):
    live = jnp.arange(lo_col.shape[0], dtype=jnp.int32) < count
    return _minmax_live(lo_col, hi_col, live & valid)


@jax.jit
def _minmax_gather(lo_col, hi_col, idx, count):
    live = jnp.arange(idx.shape[0], dtype=jnp.int32) < count
    return _minmax_live(lo_col[idx], hi_col[idx], live)


@jax.jit
def _minmax_gather_valid(lo_col, hi_col, idx, count, valid):
    live = jnp.arange(idx.shape[0], dtype=jnp.int32) < count
    return _minmax_live(lo_col[idx], hi_col[idx], live & valid[idx])


# --------------------------------------------------------------------------
# vertex scans: probe half + build half over one survivor set
# --------------------------------------------------------------------------


class VertexScan:
    """One vertex's transfer step. `probe` applies the (LIP-ordered)
    incoming filters; `build` emits an outgoing filter from the same
    survivor set — the probe→build pair is one logical scan.

    `probe_range` / `gather_live` are the adaptive scheduler's hooks
    (DESIGN.md §11): a min-max pre-filter over the raw keys, and the
    live-row key values an emitted filter's own range is computed from.
    Both are host-side control-plane ops — the raw composite key is
    host-resident for every backend (`Vertex.key`)."""

    #: live count after each filter of the last `probe` call (the
    #: adaptive scheduler's estimated-vs-actual selectivity feedback)
    live_after: Sequence[int] = ()

    def probe(self, incoming: Sequence[Tuple[np.ndarray, EngineKeys]]
              ) -> int:
        raise NotImplementedError

    @property
    def mask(self) -> np.ndarray:
        raise NotImplementedError

    @property
    def live(self) -> int:
        raise NotImplementedError

    def build(self, ek: EngineKeys, nblocks: int,
              valid: Optional[np.ndarray] = None):
        """Emit filter words from the live set; rows where `valid` is
        False are additionally excluded from the *build only* (the
        NULL-tight contract: NULL keys never match, so they never need
        filter bits — the vertex's own mask is untouched)."""
        raise NotImplementedError

    def probe_range(self, raw: np.ndarray, lo: int, hi: int,
                    ek: Optional[EngineKeys] = None) -> int:
        """Shrink the live set to rows with lo <= raw <= hi. Returns
        the number of rows tested (the live count going in). When `ek`
        (the same column's hash state) is given, device-resident scans
        run the cut on device from the cached key halves — one scalar
        sync instead of a survivor-id sync."""
        raise NotImplementedError

    def gather_live(self, raw: np.ndarray) -> np.ndarray:
        """Values of `raw` (a full-column host array) at the live rows."""
        raise NotImplementedError

    def key_range(self, raw: np.ndarray,
                  ek: Optional[EngineKeys] = None,
                  valid: Optional[np.ndarray] = None):
        """(lo, hi) int64 min/max of `raw` over the live (and `valid`)
        rows, or None when no such row exists. Device-resident scans
        reduce on device and sync 16 bytes; everyone else gathers."""
        vals = self.gather_live(raw)
        if valid is not None:
            vals = vals[self.gather_live(np.asarray(valid, bool))]
        if vals.size == 0:
            return None
        return int(vals.min()), int(vals.max())

    def live_hashes(self, ek: EngineKeys) -> np.ndarray:
        """uint32 block hashes of the live rows (the KMV distinct
        estimator's input — shares `EngineKeys`' hash cache with the
        build that follows)."""
        raise NotImplementedError

    def clear(self) -> None:
        """Empty the live set without testing a row (a disjoint min-max
        range proved no row can survive)."""
        raise NotImplementedError


class _NumpyScan(VertexScan):
    def __init__(self, mask: np.ndarray, k: int):
        self._k = k
        self._mask0 = np.asarray(mask, bool)
        # _alive is the survivor index set; None means "every masked row"
        # — and when the mask is all-True, probes and builds run on the
        # raw hash arrays with no index materialization or gathers
        self._alive: Optional[np.ndarray] = None
        self._full: Optional[bool] = None          # lazy mask0.all()
        self._probed = False
        self._mask_out: Optional[np.ndarray] = None

    def _is_full(self) -> bool:
        if self._full is None:
            self._full = bool(self._mask0.all())
        return self._full

    def probe(self, incoming):
        if not incoming:
            self.live_after = []
            return 0
        faultinject.fire("engine.probe")
        if self._alive is None and not self._is_full():
            self._alive = np.flatnonzero(self._mask0)
        packed = pack_filters([w for w, _ in incoming], self._k)
        counts: list = []
        self._alive, rows = probe_packed_np(
            packed, [ek for _, ek in incoming], self._alive,
            len(self._mask0), live_after=counts)
        self.live_after = counts
        self._probed = True
        self._mask_out = None
        return rows

    def probe_range(self, raw, lo, hi, ek=None):
        if self._alive is None and not self._is_full():
            self._alive = np.flatnonzero(self._mask0)
        if self._alive is None:
            rows = len(self._mask0)
            keep = (raw >= lo) & (raw <= hi)
            if not keep.all():
                self._alive = np.flatnonzero(keep)
        else:
            rows = int(self._alive.size)
            vals = raw[self._alive]
            keep = (vals >= lo) & (vals <= hi)
            if not keep.all():
                self._alive = self._alive[keep]
        self._probed = True
        self._mask_out = None
        return rows

    def gather_live(self, raw):
        if self._alive is not None:
            return raw[self._alive]
        if self._is_full():
            return raw
        return raw[self._mask0]

    def live_hashes(self, ek):
        if self._alive is None and not self._is_full():
            self._alive = np.flatnonzero(self._mask0)
        return ek.hga(self._alive)[0]

    def clear(self):
        self._alive = np.empty(0, np.int64)
        self._probed = True
        self._mask_out = None

    @property
    def mask(self):
        if not self._probed or self._alive is None:
            return self._mask0          # alive None after probe => all hit
        if self._mask_out is None:
            out = np.zeros(len(self._mask0), bool)
            out[self._alive] = True
            self._mask_out = out
        return self._mask_out

    @property
    def live(self):
        if self._alive is not None:
            return int(self._alive.size)
        if self._is_full():
            return len(self._mask0)
        return int(np.count_nonzero(self._mask0))

    def build(self, ek, nblocks, valid=None):
        faultinject.fire("engine.build")
        if self._alive is None and not self._is_full():
            self._alive = np.flatnonzero(self._mask0)
        alive = self._alive
        if valid is not None:
            # NULL-tight: invalid-key rows leave the *build* set only
            if alive is None:
                if not valid.all():
                    alive = np.flatnonzero(valid)
            else:
                alive = alive[valid[alive]]
        return build_alive_np(ek, alive, nblocks, self._k)


class _DeviceScan(VertexScan):
    """Shared jax/pallas scan over a *compacted* survivor set.

    The working set is a device array of original row ids, re-bucketed
    (power-of-two, TILE floor for pallas) after every filter — so later
    filters probe ~survivors, not the full padded column, mirroring the
    host mirror's early exit at bucket granularity. Rows are `(idx,
    count)`: the first `count` entries are live, the tail is padding
    (clipped to row 0, masked by an iota compare — no separate validity
    array to maintain).

    Builds read the survivor ids; off-TPU the jax engine routes them
    through the bit-identical host mirror (`build_alive_np`), because
    XLA:CPU serializes the build's scatter (~1 µs/row — measured 30x
    slower than the host mirror); on TPU the device build runs from the
    same compacted ids."""

    def __init__(self, mask: np.ndarray, engine: "BloomEngine"):
        self._e = engine
        self._n = len(mask)
        mask = np.asarray(mask, bool)
        if mask.all():
            self._idx = None                 # identity: all rows live
            self._count = self._n
            self._bucket = engine.bucket(self._n)
        else:
            host_idx = np.flatnonzero(mask).astype(np.int32)
            self._count = int(host_idx.size)
            self._bucket = engine.bucket(self._count)
            self._idx = _pad(host_idx, self._bucket)
            if not engine.host_compact:
                self._idx = device_plane.to_device(self._idx)
        self._mask_out: Optional[np.ndarray] = None
        # host copy of a *device* survivor-id array, synced at most once
        # per state (invalidated whenever the live set changes)
        self._hidx: Optional[np.ndarray] = None

    def probe(self, incoming):
        if not incoming:
            self.live_after = []
            return 0
        faultinject.fire("engine.probe")
        if self._e.device_resident:
            return self._probe_fused(incoming)
        rows = 0
        counts: list = []
        self.live_after = counts
        for words, ek in incoming:
            if self._count == 0:
                counts.append(0)
                continue
            rows += self._count
            words = device_plane.to_device(words)
            ok = self._e.probe_idx(words, ek, self._idx, self._count,
                                   self._n)
            if self._e.host_compact:
                # off-TPU: XLA's sized-nonzero is O(n) scan-heavy and the
                # count sync materializes the mask anyway — compact the
                # tiny survivor-id array on host
                live = np.flatnonzero(device_plane.to_host(ok))
                count = int(live.size)
                if count != self._count:
                    self._bucket = self._e.bucket(count)
                    ids = live.astype(np.int32) if self._idx is None \
                        else np.asarray(self._idx)[live]
                    self._idx = _pad(ids, self._bucket)
            else:
                count = device_plane.scalar(ok.sum())
                if count != self._count:
                    self._bucket = self._e.bucket(count)
                    self._idx = _compact(ok, self._idx, self._bucket)
            if count != self._count:
                self._count = count
                self._mask_out = None
                self._hidx = None
            counts.append(self._count)
        return rows

    def _probe_fused(self, incoming):
        """Device-resident probe: one jit graph applies every incoming
        filter; the host syncs a single per-filter counts vector for the
        whole vertex. Only if a filter removed rows are the survivors
        then compacted on device, into their own bucket's slots."""
        if self._count == 0:
            self.live_after = [0] * len(incoming)
            return 0
        words_dev = tuple(device_plane.to_device(w) for w, _ in incoming)
        ok, dcounts = self._e.fused_probe_idx(
            words_dev, [ek for _, ek in incoming], self._idx,
            self._count, self._n)
        device_plane.count_fused()
        width = ok.shape[0]
        tile = self._e.probe_tile(width)
        device_plane.count_probe_walk(-(-self._count // tile), tile, width)
        # the vertex's ONE d2h sync
        host_counts = device_plane.to_host(dcounts)
        self.live_after = [int(c) for c in host_counts]
        # rows-probed accounting matches the sequential path: filter f
        # "sees" the rows still live when it runs (the device walks the
        # tiles up to the last live row, `probe_rows_walked`)
        rows = self._count + int(host_counts[:-1].sum())
        new_count = int(host_counts[-1])
        if new_count != self._count:
            size = self._e.bucket(new_count)
            device_plane.count_compact(size, ok.shape[0])
            self._idx = self._e.fused_compact(ok, self._idx, size)
            self._bucket = size
            self._count = new_count
            self._mask_out = None
            self._hidx = None
        return rows

    def probe_range(self, raw, lo, hi, ek=None):
        """Range pre-filter. Device-resident scans cut on device from
        the cached key halves (signed int64 = unsigned lexicographic
        over (hi ^ sign, lo)) and sync one scalar; otherwise the
        survivor-id array is synced and tested on host — the same
        host-compaction idiom the off-TPU probe path uses."""
        if self._count == 0:
            return 0
        if self._e.device_resident and ek is not None:
            return self._probe_range_dev(ek, lo, hi)
        idx = self._host_idx()
        vals = raw if idx is None else raw[idx]
        rows = self._count
        keep = (vals >= lo) & (vals <= hi)
        if not keep.all():
            live = (np.flatnonzero(keep) if idx is None
                    else idx[keep]).astype(np.int32)
            self._count = int(live.size)
            self._bucket = self._e.bucket(self._count)
            self._idx = _pad(live, self._bucket)
            if not self._e.host_compact:
                self._idx = device_plane.to_device(self._idx)
            self._mask_out = None
            self._hidx = None
        return rows

    def _probe_range_dev(self, ek, lo, hi):
        rows = self._count
        dlo, dhi = ek.dev(self._e.bucket(self._n))
        blo_lo, _, blo_hi = _bound_halves(lo)
        bhi_lo, _, bhi_hi = _bound_halves(hi)
        if self._idx is None:
            idx, cnt = _range_cut_count(dlo, dhi, self._count, blo_lo,
                                        blo_hi, bhi_lo, bhi_hi)
        else:
            idx, cnt = _range_cut_gather(dlo, dhi, self._idx,
                                         self._count, blo_lo, blo_hi,
                                         bhi_lo, bhi_hi)
        new_count = device_plane.scalar(cnt)
        if new_count != self._count:
            new_bucket = self._e.bucket(new_count)
            if new_bucket != self._bucket:
                idx = idx[:new_bucket]
                self._bucket = new_bucket
            self._idx = idx
            self._count = new_count
            self._mask_out = None
            self._hidx = None
        return rows

    def key_range(self, raw, ek=None, valid=None):
        if self._count == 0:
            return None
        if not (self._e.device_resident and ek is not None):
            return super().key_range(raw, ek=ek, valid=valid)
        b = self._e.bucket(self._n)
        dlo, dhi = ek.dev(b)
        if valid is None:
            q = (_minmax_count(dlo, dhi, self._count)
                 if self._idx is None else
                 _minmax_gather(dlo, dhi, self._idx, self._count))
        else:
            v = device_plane.to_device(_pad(np.asarray(valid, bool), b,
                                            False))
            q = (_minmax_count_valid(dlo, dhi, self._count, v)
                 if self._idx is None else
                 _minmax_gather_valid(dlo, dhi, self._idx, self._count,
                                      v))
        qh = device_plane.to_host(q)
        lo = _val_from_halves(qh[0], qh[1])
        hi = _val_from_halves(qh[2], qh[3])
        if lo > hi:             # every live row was invalid
            return None
        return lo, hi

    def gather_live(self, raw):
        idx = self._host_idx()
        return raw if idx is None else raw[idx]

    def live_hashes(self, ek):
        return ek.hga(self._host_idx())[0]

    def clear(self):
        self._count = 0
        self._bucket = self._e.bucket(0)
        self._idx = _pad(np.empty(0, np.int32), self._bucket)
        if not self._e.host_compact:
            self._idx = device_plane.to_device(self._idx)
        self._mask_out = None
        self._hidx = None

    def _host_idx(self) -> Optional[np.ndarray]:
        """Live original row ids on host (None = every row). A device
        survivor-id array syncs once and is cached until the live set
        changes."""
        if self._idx is None:
            return None
        if not isinstance(self._idx, np.ndarray):
            if self._hidx is None:
                out = device_plane.to_host(self._idx)
                self._hidx = out[: self._count].astype(np.int64)
            return self._hidx
        return np.asarray(self._idx)[: self._count].astype(np.int64)

    @property
    def mask(self):
        if self._mask_out is None:
            idx = self._host_idx()
            if idx is None:
                self._mask_out = np.ones(self._n, bool)
            else:
                out = np.zeros(self._n, bool)
                out[idx] = True
                self._mask_out = out
        return self._mask_out

    @property
    def live(self):
        return self._count

    def build(self, ek, nblocks, valid=None):
        faultinject.fire("engine.build")
        if self._e.host_build:
            idx = self._host_idx()
            if valid is not None:
                # NULL-tight: intersect the live ids with the validity
                # mask on host (same control-plane idiom as compaction)
                if idx is None:
                    if not valid.all():
                        idx = np.flatnonzero(valid).astype(np.int64)
                else:
                    idx = idx[valid[idx]]
            # host-mirror words stay host: the probe that consumes them
            # uploads (and counts) them once; returning a device copy
            # here would add a d2h when the artifact cache stores them
            return build_alive_np(ek, idx, nblocks, self._e.k)
        return self._e.build_idx(ek, self._idx, self._count, self._n,
                                 nblocks, valid=valid)


# --------------------------------------------------------------------------
# engines
# --------------------------------------------------------------------------


class BloomEngine:
    """Backend-pluggable batched Bloom runtime. Subclasses provide the
    raw ops; this base provides the strategy-facing API:

    * ``keys(values)``            — hash a key column once;
    * ``begin(mask)``             — open a `VertexScan`;
    * ``build_filter`` / ``probe_filter`` — one-shot ops (Bloom-Join,
      benches, tests)."""

    backend = "base"
    #: device engines set True off-TPU: filter builds run through the
    #: bit-identical host mirror (XLA:CPU serializes the build scatter)
    host_build = False
    #: device engines set True off-TPU: survivor compaction runs on host
    #: (XLA:CPU's sized-nonzero is scan-heavy; the mask is synced for the
    #: live count regardless)
    host_compact = False
    #: the device-resident data plane (DESIGN.md §15): fused multi-filter
    #: probes, device compaction/range-cut/min-max, device builds — the
    #: host syncs scalars and tiny counts vectors only. Default on TPU;
    #: forceable off-TPU (pallas-interpret validation, `ExecConfig.device`)
    device_resident = False

    def __init__(self, k: int = DEFAULT_K):
        self.k = k

    # -- device-scan hooks (jax/pallas) --------------------------------
    def probe_idx(self, words, ek: "EngineKeys", idx, count: int,
                  n: int):
        """Probe `words` over the compacted survivor ids (None =
        identity); returns a device bool mask with padding False."""
        raise NotImplementedError

    def fused_probe_idx(self, words, eks, idx, count: int, n: int):
        """One device pass over every incoming filter: returns (device
        bool survivor mask over the `idx` width, device int32
        live-count-after-each-filter vector) — the caller syncs the
        counts once per vertex."""
        raise NotImplementedError

    def probe_tile(self, width: int) -> int:
        """Rows per step of `fused_probe_idx`'s walk over a `width`-row
        probe; the walk stops after the tile that holds the last live
        row. One tile of the whole width: the probe runs over all of
        it."""
        return width

    def fused_compact(self, ok, idx, size: int):
        """The survivor row ids of a `fused_probe_idx` mask (`idx` as
        passed to it) in `size` slots, front-packed and zero-filled: the
        search runs over `size` queries, not over the mask's width."""
        return _compact(ok, idx, size)

    def build_idx(self, ek: "EngineKeys", idx, count: int, n: int,
                  nblocks: int, valid: Optional[np.ndarray] = None):
        raise NotImplementedError

    # -- strategy-facing ----------------------------------------------
    def keys(self, values: np.ndarray) -> EngineKeys:
        raise NotImplementedError

    def begin(self, mask: np.ndarray) -> VertexScan:
        raise NotImplementedError

    def bucket(self, n: int) -> int:
        return _bucket(n)

    def build_filter(self, ek: EngineKeys,
                     mask: Optional[np.ndarray] = None,
                     bits_per_key: int = DEFAULT_BITS_PER_KEY,
                     nblocks: Optional[int] = None,
                     valid: Optional[np.ndarray] = None) -> BloomFilter:
        """`valid=False` rows are excluded from the build (and the
        sizing) — the NULL-tight hook: NULL join keys never match, so
        they never earn filter bits."""
        if valid is not None:
            valid = np.asarray(valid, bool)
            if valid.all():
                valid = None
        if mask is None:
            n_live = len(ek) if valid is None else int(valid.sum())
        else:
            mask = np.asarray(mask, bool)
            n_live = int(mask.sum()) if valid is None \
                else int((mask & valid).sum())
        ins = np.ones(len(ek), bool) if mask is None else mask
        if nblocks is None:
            nblocks = blocks_for(max(n_live, 1), bits_per_key)
        scan = self.begin(ins)
        return BloomFilter(scan.build(ek, nblocks, valid=valid), self.k)

    def probe_filter(self, filt: BloomFilter, ek: EngineKeys,
                     live: Optional[np.ndarray] = None) -> np.ndarray:
        scan = self.begin(np.ones(len(ek), bool) if live is None
                          else np.asarray(live, bool))
        scan.probe([(filt.words, ek)])
        return scan.mask

    # -- distributed hook ---------------------------------------------
    def make_distributed_transfer(self, mesh, live_keys: int,
                                  bits_per_key: int = DEFAULT_BITS_PER_KEY,
                                  axis: str = "data",
                                  tree_or: bool = False):
        """Sharded one-edge transfer (build → OR all-reduce → probe),
        filter sized by the building relation's live keys. The engine is
        the sizing/padding authority; `repro.core.distributed` owns the
        collectives."""
        from repro.core import distributed
        nblocks = blocks_for(max(live_keys, 1), bits_per_key)
        return distributed.make_distributed_transfer(
            mesh, nblocks, k=self.k, axis=axis, tree_or=tree_or)

    def shard_keys(self, keys: np.ndarray, mesh, axis: str = "data"):
        """Row-shard a key column, padding each shard to a power-of-two
        bucket so resharded re-runs reuse the jit cache."""
        from repro.core import distributed
        return distributed.shard_table_arrays(keys, mesh, axis,
                                              bucket=True)


class NumpyEngine(BloomEngine):
    """Host mirror backend — the relational executor's CPU wall-clock
    path (DESIGN.md §7)."""

    backend = "numpy"

    def keys(self, values):
        with device_plane.span("transfer.keys"):
            keys = np.asarray(values).astype(np.int64, copy=False)
            if not keys.flags.c_contiguous:
                keys = np.ascontiguousarray(keys)
        # lazy: EngineKeys.hga hashes the full column once on first
        # mostly-alive use, or just the survivor subset when earlier
        # filters already shrank the working set
        return EngineKeys(len(keys), raw=keys)

    def begin(self, mask):
        return _NumpyScan(mask, self.k)


class JaxEngine(BloomEngine):
    """jit'd `repro.core.bloom` ops over bucketed, survivor-compacted
    batches: device hash state per column is computed once
    (`EngineKeys.dev_hashed`), every probe is the hashed block-row
    gather, and off-TPU builds run through the host mirror."""

    backend = "jax"

    def __init__(self, k: int = DEFAULT_K,
                 device_resident: Optional[bool] = None):
        super().__init__(k)
        on_tpu = jax.default_backend() == "tpu"
        if device_resident is None:
            device_resident = on_tpu
        self.device_resident = bool(device_resident)
        # device-resident mode keeps builds and compaction on device even
        # off-TPU (the CI validation posture); otherwise off-TPU routes
        # both through the bit-identical host mirrors. On TPU, XLA's
        # compiler takes 17-21 s per (keys, blocks) shape over the build
        # scatter; the compile cache keeps it for later processes
        host_side = not on_tpu and not self.device_resident
        self.host_build = host_side
        self.host_compact = host_side

    def keys(self, values):
        with device_plane.span("transfer.keys"):
            lo, hi = hashing.key_halves(np.asarray(values))
        return EngineKeys(len(lo), lo=lo, hi=hi)

    def begin(self, mask):
        return _DeviceScan(mask, self)

    def probe_idx(self, words, ek, idx, count, n):
        h, g1, g2 = ek.dev_hashed(self.bucket(n))
        if idx is None:
            return _probe_hashed_count(words, h, g1, g2, count, self.k)
        return _probe_hashed_gather(words, h, g1, g2, idx, count, self.k)

    def fused_probe_idx(self, words, eks, idx, count, n):
        b = self.bucket(n)
        hs, g1s, g2s = zip(*(ek.dev_hashed(b) for ek in eks))
        if idx is None:
            return _fused_probe_count(words, hs, g1s, g2s, count, self.k)
        return _fused_probe_gather(words, hs, g1s, g2s, idx, count,
                                   self.k)

    def build_idx(self, ek, idx, count, n, nblocks, valid=None):
        lo, hi = ek.dev(self.bucket(n))
        if valid is not None:
            v = device_plane.to_device(_pad(np.asarray(valid, bool),
                                            self.bucket(n), False))
            if idx is None:
                return _build_count_valid(lo, hi, v, count, nblocks,
                                          self.k)
            return _build_gather_valid(lo, hi, idx, v, count, nblocks,
                                       self.k)
        if idx is None:
            return _build_count(lo, hi, count, nblocks, self.k)
        return _build_gather(lo, hi, idx, count, nblocks, self.k)



class PallasEngine(BloomEngine):
    """`repro.kernels.bloom` TPU kernels (interpret mode on the cpu
    platform, `repro.kernels.resolve_interpret`).
    Buckets are TILE-aligned (the kernels' grid contract)."""

    backend = "pallas"

    def __init__(self, k: int = DEFAULT_K,
                 interpret: Optional[bool] = None,
                 device_resident: Optional[bool] = None):
        super().__init__(k)
        from repro.kernels import resolve_interpret
        on_tpu = jax.default_backend() == "tpu"
        self.interpret = resolve_interpret(interpret)
        if device_resident is None:
            device_resident = on_tpu
        self.device_resident = bool(device_resident)
        # builds stay on the Pallas kernels (interpret mode is the
        # off-TPU validation harness); compaction goes host-side unless
        # the device-resident plane keeps survivor ids on device
        self.host_compact = not on_tpu and not self.device_resident

    def keys(self, values):
        with device_plane.span("transfer.keys"):
            lo, hi = hashing.key_halves(np.asarray(values))
        return EngineKeys(len(lo), lo=lo, hi=hi)

    def begin(self, mask):
        return _DeviceScan(mask, self)

    def bucket(self, n):
        from repro.kernels.bloom import bloom as _k
        return _bucket(n, floor=_k.TILE)

    def probe_idx(self, words, ek, idx, count, n):
        lo, hi = ek.dev(self.bucket(n))
        if idx is not None:
            lo, hi = _gather2(lo, hi, idx)
        return _mask_count(self.probe_op(words, lo, hi), count)

    def fused_probe_idx(self, words, eks, idx, count, n):
        b = self.bucket(n)
        los, his = zip(*(ek.dev(b) for ek in eks))
        if idx is None:
            return _fused_pallas_count(words, los, his, count, self.k,
                                       self.probe_tile(b), self.interpret)
        return _fused_pallas_gather(words, los, his, idx, count, self.k,
                                    self.probe_tile(idx.shape[0]),
                                    self.interpret)

    def probe_tile(self, width):
        from repro.kernels.bloom import bloom as _k
        return _k.walk_tile(width)

    def fused_compact(self, ok, idx, size):
        if idx is None:
            return _fused_pallas_count_compact(ok, size)
        return _fused_pallas_gather_compact(ok, idx, size)

    def build_idx(self, ek, idx, count, n, nblocks, valid=None):
        lo, hi = ek.dev(self.bucket(n))
        vdev = None if valid is None else device_plane.to_device(
            _pad(np.asarray(valid, bool), self.bucket(n), False))
        if idx is not None:
            lo, hi = _gather2(lo, hi, idx)
            mask = _iota_mask(idx.shape[0], count)
            if vdev is not None:
                mask = mask & vdev[idx]
        else:
            mask = _iota_mask(lo.shape[0], count)
            if vdev is not None:
                mask = mask & vdev
        return self.build_op(lo, hi, mask, nblocks)

    def probe_op(self, words, lo, hi):
        from repro.kernels.bloom import bloom as _k
        return _k.probe_pallas(words, lo, hi, k=self.k,
                               interpret=self.interpret)

    def build_op(self, lo, hi, mask, nblocks):
        from repro.kernels.bloom import bloom as _k
        return _k.build_pallas(lo, hi, mask, nblocks, k=self.k,
                               interpret=self.interpret)


_ENGINES: Dict[Tuple, BloomEngine] = {}
_ENGINES_LOCK = threading.Lock()


def get_engine(backend: str = "numpy", k: int = DEFAULT_K,
               interpret: Optional[bool] = None,
               device_resident: Optional[bool] = None) -> BloomEngine:
    """Engine instances are cached so jit/pallas caches and key-hash
    device pads are shared across strategies and queries. Creation is
    locked so concurrent sessions (repro.serve) agree on one instance
    per key instead of silently forking the shared jit caches
    (DESIGN.md §12 thread-safety contract).

    `device_resident=None` resolves to the backend default (on iff a
    real TPU is attached); True forces the device-resident plane off-TPU
    (pallas-interpret validation, the `ExecConfig.device="on"` path)."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown bloom backend {backend!r}; "
                         f"choose from {BACKENDS}")
    if backend == "numpy":
        device_resident = None      # host mirror: no device to reside on
    key = (backend, k, interpret if backend == "pallas" else None,
           device_resident)
    with _ENGINES_LOCK:
        eng = _ENGINES.get(key)
        if eng is None:
            if backend == "numpy":
                eng = NumpyEngine(k)
            elif backend == "jax":
                eng = JaxEngine(k, device_resident=device_resident)
            else:
                eng = PallasEngine(k, interpret=interpret,
                                   device_resident=device_resident)
            _ENGINES[key] = eng
    return eng
