"""Pallas TPU kernels: blocked-Bloom build / probe / transfer.

TPU adaptation (DESIGN.md §3): the filter is an array of 256-bit blocks
(8 × uint32 lanes). One hash selects the block; k bit positions are
derived by double hashing *within* the block, so a probe reads k words of
one block and an insert read-modify-writes one block.

Layout, as the v5e compiler accepts it:

* **keys stream lane-dense.** A key column of n rows (n % TILE == 0) is
  viewed as (n/128, 128) and tiled in (rows, 128) blocks, rows a
  multiple of 8, over a 1-D grid.
* **probe: a walk over the live prefix in tiles.** A probe of n rows
  of which the first `count` are live (the survivors, front-packed)
  walks them in tiles of `walk_tile(n)` rows (at most `PROBE_TILE`) in
  a `lax.fori_loop` whose trip count, ceil(count / tile), is computed
  on the device from the traced `count`: tiles past the last live row
  are never walked, and no shape depends on `count`. Each step slices
  its tile's key halves (or gathers them at the tile's survivor ids),
  hashes them, fetches their block rows, runs the Pallas kernel over
  the tile, masks the rows past `count`, adds each filter's live count
  and writes the tile's survivor mask into the (n,) output. So the
  probe's working memory is one tile's, whatever the bucket.
* **block rows fetched by XLA, per tile.** The vector unit has no
  gather from VMEM, and an (nblocks, 8) filter kept in VMEM would pad
  its 8 lanes to 128 (16x its size). So an XLA gather ahead of the
  kernel fetches each of the tile's keys' block row from the HBM filter
  as 8 lane-dense word planes (`_probe_rows`; a row gather, because the
  TPU compiler spends tens of seconds on the (nblocks, 8) -> flat
  relayout a word gather needs at 2^15-2^17 blocks; over a whole 2^25
  bucket the (rows, 8) gather and its transpose took more HBM than the
  chip has), and the kernel hashes, picks the k probed words, tests
  their bits, and ANDs across every filter of a vertex (the cumulative
  survivor mask of the tile after each filter).
* **build: filter resident in VMEM, lane-dense.** The filter accumulates
  in its (nblocks/16, 128) view — 16 blocks per 128-lane row, no
  padding — for all grid steps. Each key's block index and packed bit
  positions arrive in SMEM tiles, and a serialized scalar loop
  read-modify-writes one (1, 128) row per key (scatter-OR has no vector
  primitive on the VPU). Filters above `VMEM_FILTER_MAX` are refused.

All kernels are bit-exact against the ref.py oracle.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing
from repro.core.bloom import (
    BLOCK_BITS, DEFAULT_K, LANES, _block_index, _positions,
)
from repro.core.hashing import GOLDEN
from repro.kernels import resolve_interpret

TILE = 1024  # keys per build grid step; key counts are multiples of TILE
_LANE = 128
_BLOCKS_PER_ROW = _LANE // LANES      # 16 filter blocks per 128-lane row
_MAX_ROWS = 64                        # probe grid step: up to 64 x 128 keys
#: rows per step of a probe's walk over its live prefix (`walk_tile`).
#: Compiled for a v5e, a 4-filter probe's temp is 0.21 GiB at 2^18
#: whatever the bucket (0.35 at 2^19, 0.63 at 2^20, 1.19 at 2^21), and
#: a 2^25-row bucket takes at most 128 steps
PROBE_TILE = 1 << 18
#: largest filter the build kernel keeps resident in VMEM (v5e: 128 MiB)
VMEM_FILTER_MAX = 64 << 20

# murmur3 constants as numpy scalars: pallas kernels may not capture
# module-level device arrays, but numpy scalars become in-trace literals
_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_P2 = np.uint32(0x7FEB352D)


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * _C1
    h = h ^ (h >> 13)
    h = h * _C2
    h = h ^ (h >> 16)
    return h


def walk_tile(n: int) -> int:
    """Rows per step of a probe's walk over n rows: the largest power of
    two up to `PROBE_TILE` dividing n (n % TILE == 0 keeps it >= TILE
    while `PROBE_TILE` >= TILE)."""
    t = PROBE_TILE
    while n % t:
        t //= 2
    return t


def _tile_rows(n: int) -> int:
    """Rows of 128 keys per probe grid step: the largest power of two
    up to `_MAX_ROWS` dividing n/128 (n % TILE == 0 keeps it >= 8)."""
    rows, br = n // _LANE, _MAX_ROWS
    while rows % br:
        br //= 2
    return br


# --------------------------------------------------------------------------
# probe
# --------------------------------------------------------------------------


def _probe_rows(words, lo, hi):
    """XLA stage of a probe: block hash h [n] and each key's 256-bit
    block row as 8 word planes [LANES, n], gathered from the HBM
    filter."""
    h = hashing.hash64(lo, hi)
    blk = _block_index(h, words.shape[0]).astype(jnp.int32)
    return h, words[blk].T


def _probe_kernel(*refs, k: int, m: int):
    out_ref = refs[-1]
    ok = None
    for f in range(m):
        h = refs[2 * f][...]
        row_ref = refs[2 * f + 1]
        rows = [row_ref[lane] for lane in range(LANES)]
        g1 = _fmix32(h ^ GOLDEN)
        g2 = _fmix32(h ^ _P2) | np.uint32(1)
        for j in range(k):
            pos = (g1 + np.uint32(j) * g2) & np.uint32(BLOCK_BITS - 1)
            lane = pos >> 5
            w = rows[0]
            for x in range(1, LANES):
                w = jnp.where(lane == np.uint32(x), rows[x], w)
            hit = ((w >> (pos & np.uint32(31))) & np.uint32(1)) \
                == np.uint32(1)
            ok = hit if ok is None else ok & hit
        out_ref[f] = ok.astype(jnp.int32)


def _probe_tile(words_list, los, his, k: int, interpret: bool):
    """The Pallas probe of m filters over one tile of t rows (every
    lo/hi uint32 [t], t % TILE == 0): bool [m, t], row f the cumulative
    survivor mask after filters 0..f."""
    m = len(words_list)
    n = los[0].shape[0]
    rows = n // _LANE
    br = _tile_rows(n)
    args, in_specs = [], []
    for words, lo, hi in zip(words_list, los, his):
        h, planes = _probe_rows(words, lo, hi)
        args += [h.reshape(rows, _LANE),
                 planes.reshape(LANES, rows, _LANE)]
        in_specs += [pl.BlockSpec((br, _LANE), lambda i: (i, 0)),
                     pl.BlockSpec((LANES, br, _LANE), lambda i: (0, i, 0))]
    out = pl.pallas_call(
        functools.partial(_probe_kernel, k=k, m=m),
        grid=(rows // br,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m, br, _LANE), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, rows, _LANE), jnp.int32),
        interpret=interpret,
    )(*args)
    return out.reshape(m, n) != 0


@functools.partial(jax.jit, static_argnames=("k", "tile", "interpret"))
def multi_probe_pallas(words_list, los, his, count, idx=None,
                       k: int = DEFAULT_K, tile: Optional[int] = None,
                       interpret: Optional[bool] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused probe of m filters over the live prefix of one row set.

    `words_list`/`los`/`his` are equal-length tuples of filters and the
    uint32 key halves they probe. Row i is `los[f][i]` or, with `idx`
    (int32 [n]), `los[f][idx[i]]`; rows i < `count` are live. The walk
    takes ceil(count / tile) steps of `tile` rows (default
    `walk_tile(n)`; a power of two >= TILE dividing n). Returns
    (ok bool [n], counts int32 [m]): ok is the AND of every filter over
    the live rows, False past `count`; counts[f] is the number of live
    rows that pass filters 0..f — bit-identical to probing the filters
    one by one over the whole width, ANDing, and masking to `count`."""
    m = len(words_list)
    n = (los[0] if idx is None else idx).shape[0]
    t = walk_tile(n) if tile is None else tile
    assert n % t == 0 and t % TILE == 0, (n, t)
    interpret = resolve_interpret(interpret)
    count = jnp.asarray(count, jnp.int32)
    row = jnp.arange(t, dtype=jnp.int32)

    def step(i, carry):
        ok, counts = carry
        start = i * t
        if idx is None:
            lo_t = tuple(jax.lax.dynamic_slice(a, (start,), (t,))
                         for a in los)
            hi_t = tuple(jax.lax.dynamic_slice(a, (start,), (t,))
                         for a in his)
        else:
            ids = jax.lax.dynamic_slice(idx, (start,), (t,))
            lo_t = tuple(a[ids] for a in los)
            hi_t = tuple(a[ids] for a in his)
        cum = _probe_tile(words_list, lo_t, hi_t, k, interpret)
        cum = cum & (start + row < count)[None, :]
        counts = counts + jnp.sum(cum, axis=1, dtype=jnp.int32)
        return jax.lax.dynamic_update_slice(ok, cum[-1], (start,)), counts

    trips = (count + (t - 1)) // t
    return jax.lax.fori_loop(0, trips, step,
                             (jnp.zeros(n, jnp.bool_),
                              jnp.zeros(m, jnp.int32)))


def probe_pallas(words: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                 k: int = DEFAULT_K,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """words [nblocks, LANES] uint32; lo/hi uint32 [n] (n % TILE == 0),
    every row live."""
    return multi_probe_pallas((words,), (lo,), (hi,), lo.shape[0], k=k,
                              interpret=interpret)[0]


# --------------------------------------------------------------------------
# build
# --------------------------------------------------------------------------


def _insert_plan(lo, hi, mask, nblocks: int, k: int):
    """XLA stage of a build: each key's block index (-1: not inserted)
    and its k in-block bit positions (8 bits each), packed four to an
    int32 word — the scalars the insert loop reads from SMEM."""
    h = hashing.hash64(lo, hi)
    blk = jnp.where(mask, _block_index(h, nblocks).astype(jnp.int32),
                    jnp.int32(-1))
    pos = _positions(h, k)
    packed = []
    for q in range(0, k, 4):
        word = jnp.zeros(lo.shape, jnp.uint32)
        for j in range(q, min(q + 4, k)):
            word = word | (pos[:, j] << jnp.uint32(8 * (j - q)))
        packed.append(jax.lax.bitcast_convert_type(word, jnp.int32))
    return blk, packed


def _build_kernel(blk_ref, *refs, k: int):
    pos_refs, out_ref = refs[:-1], refs[-1]

    # zero the resident accumulator on the first grid step
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)

    def insert(i, carry):
        b = blk_ref[i]

        @pl.when(b >= 0)
        def _or_block():
            first = (b & (_BLOCKS_PER_ROW - 1)) * LANES  # first lane
            upd = jnp.zeros((1, _LANE), jnp.uint32)
            for j in range(k):
                p = (pos_refs[j // 4][i] >> (8 * (j % 4))) & 0xFF
                bit = jnp.uint32(1) << (p & 31).astype(jnp.uint32)
                upd = upd | jnp.where(lanes == first + (p >> 5), bit,
                                      jnp.uint32(0))
            r = b >> 4                              # its 128-lane row
            out_ref[pl.ds(r, 1), :] = out_ref[pl.ds(r, 1), :] | upd

        return carry

    jax.lax.fori_loop(0, TILE, insert, 0)


@functools.partial(jax.jit, static_argnames=("nblocks", "k", "interpret"))
def build_pallas(lo: jnp.ndarray, hi: jnp.ndarray, mask: jnp.ndarray,
                 nblocks: int, k: int = DEFAULT_K,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """Filter words uint32 [nblocks, LANES] from the `mask`ed keys."""
    n = lo.shape[0]
    assert n % TILE == 0
    nbytes = nblocks * LANES * 4
    if nbytes > VMEM_FILTER_MAX:
        raise ValueError(
            f"{nblocks}-block filter ({nbytes >> 20} MiB) exceeds the "
            f"build kernel's VMEM budget ({VMEM_FILTER_MAX >> 20} MiB)")
    rows = -(-nblocks // _BLOCKS_PER_ROW)
    blk, packed = _insert_plan(lo, hi, mask, nblocks, k)
    smem = pl.BlockSpec((TILE,), lambda i: (i,),
                        memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_build_kernel, k=k),
        grid=(n // TILE,),
        in_specs=[smem] * (1 + len(packed)),
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows, _LANE), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=rows * _LANE * 4 + (16 << 20)),
        interpret=resolve_interpret(interpret),
    )(blk, *packed)
    return out.reshape(-1)[: nblocks * LANES].reshape(nblocks, LANES)


# --------------------------------------------------------------------------
# transfer (paper §3.2 filter transformation): probe the incoming filter
# on the incoming join key, insert survivors' outgoing keys into a fresh
# filter — the probe and build kernels in one jit
# --------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("nblocks_out", "k", "interpret"))
def transfer_pallas(in_words: jnp.ndarray,
                    in_lo: jnp.ndarray, in_hi: jnp.ndarray,
                    out_lo: jnp.ndarray, out_hi: jnp.ndarray,
                    mask: jnp.ndarray, nblocks_out: int,
                    k: int = DEFAULT_K, interpret: Optional[bool] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    ok = mask & probe_pallas(in_words, in_lo, in_hi, k=k,
                             interpret=interpret)
    return ok, build_pallas(out_lo, out_hi, ok, nblocks_out, k=k,
                            interpret=interpret)
