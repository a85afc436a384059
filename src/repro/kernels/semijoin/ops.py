"""Public wrappers for the semijoin kernel."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing
from repro.kernels.semijoin import semijoin as _k


def _pad_to_tile(a: np.ndarray, fill=0) -> np.ndarray:
    n = len(a)
    m = ((n + _k.TILE - 1) // _k.TILE) * _k.TILE
    if m == n:
        return a
    out = np.full(m, fill, dtype=a.dtype)
    out[:n] = a
    return out


def capacity_for(n: int) -> int:
    """Power-of-two capacity at <=50% load."""
    cap = 2 * max(int(n), 1)
    return max(int(2 ** np.ceil(np.log2(cap))), _k.TILE // 2)


def semijoin_build(keys: np.ndarray, mask: Optional[np.ndarray] = None,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    keys = np.asarray(keys)
    if mask is None:
        mask = np.ones(len(keys), bool)
    cap = capacity_for(len(keys))
    lo, hi = hashing.key_halves(_pad_to_tile(keys))
    m = _pad_to_tile(np.asarray(mask, bool), False)
    return _k.build_pallas(jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(m),
                           cap, interpret=interpret)


def semijoin_probe(table, keys: np.ndarray,
                   interpret: Optional[bool] = None) -> np.ndarray:
    klo, khi, occ = table
    keys = np.asarray(keys)
    lo, hi = hashing.key_halves(_pad_to_tile(keys))
    out = _k.probe_pallas(klo, khi, occ, jnp.asarray(lo), jnp.asarray(hi),
                          interpret=interpret)
    return np.asarray(out)[: len(keys)]


def semi_mask(probe_keys: np.ndarray, build_keys: np.ndarray,
              build_mask: Optional[np.ndarray] = None,
              interpret: Optional[bool] = None) -> np.ndarray:
    """R ⋉ S membership mask, end to end through the Pallas kernels."""
    table = semijoin_build(build_keys, build_mask, interpret=interpret)
    return semijoin_probe(table, probe_keys, interpret=interpret)


# --------------------------------------------------------------------------
# joinmap: build with row payload + lookup (join-runtime primitive)
# --------------------------------------------------------------------------
#
# The jnp mirrors insert rows in the same sequential order as the Pallas
# build kernel, so both builders produce the identical table layout and
# can be mixed freely (the engine builds with jnp off-TPU, where the
# interpreter would serialize the insert loop at Python speed, while the
# lookup still exercises the Pallas kernel in interpret mode).


@functools.partial(jax.jit, static_argnames=("cap",))
def _joinmap_build_jnp(lo, hi, mask, cap: int):
    h = _k._slot_hash(lo, hi)

    def insert(i, state):
        klo, khi, occ, row = state

        def cond(s):
            occupied = occ[s] != 0
            same = (klo[s] == lo[i]) & (khi[s] == hi[i])
            return occupied & ~same

        def step(s):
            return (s + 1) & (cap - 1)

        slot = jax.lax.while_loop(
            cond, step, (h[i] & jnp.uint32(cap - 1)).astype(jnp.int32))

        def store(st):
            klo, khi, occ, row = st
            return (klo.at[slot].set(lo[i]), khi.at[slot].set(hi[i]),
                    occ.at[slot].set(jnp.uint32(1)),
                    row.at[slot].set(jnp.uint32(i)))

        return jax.lax.cond(mask[i], store, lambda st: st, state)

    init = tuple(jnp.zeros(cap, jnp.uint32) for _ in range(4))
    return jax.lax.fori_loop(0, lo.shape[0], insert, init)


@jax.jit
def _joinmap_lookup_jnp(klo, khi, occ, row, lo, hi):
    cap = klo.shape[0]
    h = _k._slot_hash(lo, hi)
    slot = (h & jnp.uint32(cap - 1)).astype(jnp.int32)

    def cond(state):
        _, resolved, _ = state
        return ~jnp.all(resolved)

    def step(state):
        slot, resolved, ans = state
        s_occ = occ[slot] != 0
        hit = s_occ & (klo[slot] == lo) & (khi[slot] == hi)
        ans = jnp.where(hit & ~resolved, row[slot].astype(jnp.int32), ans)
        resolved = resolved | hit | ~s_occ
        slot = jnp.where(resolved, slot, (slot + 1) & (cap - 1))
        return slot, resolved, ans

    init = (slot, jnp.zeros(lo.shape, jnp.bool_),
            jnp.full(lo.shape, -1, jnp.int32))
    return jax.lax.while_loop(cond, step, init)[2]


def joinmap_build(keys: np.ndarray, use_pallas: bool = True,
                  interpret: Optional[bool] = None):
    """Build an open-addressing (key -> row) map. Returns
    ((klo, khi, occ, row), occupied): `occupied < len(keys)` iff the
    keys contain duplicates (equal keys dedup into one slot), which is
    the join engine's fallback signal."""
    from repro.core import device_plane as dp
    keys = np.asarray(keys)
    cap = capacity_for(len(keys))
    lo, hi = hashing.key_halves(_pad_to_tile(keys))
    mask = _pad_to_tile(np.ones(len(keys), bool), False)
    if use_pallas:
        table = _k.build_rows_pallas(dp.to_device(lo), dp.to_device(hi),
                                     dp.to_device(mask), cap,
                                     interpret=interpret)
    else:
        table = _joinmap_build_jnp(dp.to_device(lo), dp.to_device(hi),
                                   dp.to_device(mask), cap)
    occupied = dp.scalar(jnp.sum(table[2]))
    return table, occupied


def joinmap_lookup(table, keys: np.ndarray, use_pallas: bool = True,
                   interpret: Optional[bool] = None) -> np.ndarray:
    """Matched build row per probe key (int64), -1 on miss."""
    from repro.core import device_plane as dp
    klo, khi, occ, row = table
    keys = np.asarray(keys)
    lo, hi = hashing.key_halves(_pad_to_tile(keys))
    if use_pallas:
        out = _k.lookup_pallas(klo, khi, occ, row, dp.to_device(lo),
                               dp.to_device(hi),
                               interpret=interpret)
    else:
        out = _joinmap_lookup_jnp(klo, khi, occ, row, dp.to_device(lo),
                                  dp.to_device(hi))
    return dp.to_host(out)[: len(keys)].astype(np.int64)


# --------------------------------------------------------------------------
# device sorted-segment join (the device-resident data plane, DESIGN.md
# §15): duplicate-key joins on device — one lower-bound binary search of
# every probe key into the sorted live build rows, segment emission —
# with the host syncing one output-size scalar per join. Bit-identical
# (build_idx, probe_idx) to `engine_join.sorted_join_indices`: signed
# int64 keys are compared as (hi ^ sign, lo) unsigned pairs.
#
# The build side's stable order is computed on host, where its keys
# already are, and travels sorted in the build upload: XLA's TPU
# compiler takes 10-45 s per shape for a single-key sort at 2^14-2^23
# rows, and minutes for a three-key one. The build stack is
# (lo[, hi ^ sign], run, order) in that order, with `nb_live` the number
# of live rows: NULL-key and padding rows sort after every live row, so
# a search over [0, nb_live) never reaches them (NULL-key probe rows are
# handled by zeroing their match counts — no compact-and-remap on either
# side). `run` is each live row's remaining equal-key run length, so a
# probe row's match count is `run` at its lower bound and no
# right-bound search is needed. When every live build key has the same
# high half — every single-column TPC-H key does — the hi plane is left
# out and that half travels as a scalar: each search step then gathers
# one plane. Everything past the sort — search, counts, selection,
# emission — runs on device, with `bloom.prefix_sum` + binary search in
# place of scatters and cumsums for the same reason.
# --------------------------------------------------------------------------

_SIGN = np.uint32(0x80000000)


def _pow2(n: int, floor: int = 256) -> int:
    return max(floor, int(2 ** np.ceil(np.log2(max(int(n), 1)))))


def _pad_pow2(a: np.ndarray, m: int, fill=0) -> np.ndarray:
    if m == len(a):
        return a
    out = np.full(m, fill, dtype=a.dtype)
    out[: len(a)] = a
    return out


def _run_lengths(keys: np.ndarray) -> np.ndarray:
    """For sorted `keys`: the end of each row's equal-key run less the
    row's position."""
    n = len(keys)
    ends = np.append(np.flatnonzero(keys[1:] != keys[:-1]) + 1, n)
    return np.repeat(ends, np.diff(ends, prepend=0)) - np.arange(n)


def _lower_bound(keys, q, nb_live):
    """First position in [0, nb_live) whose key is not below the query's
    (nb_live where none is), over the key planes `keys` = (lo[, hi]) of
    the sorted build rows, as a fixed log2(n)-step binary-search loop (no
    pair-valued searchsorted primitive on device)."""
    n = keys[0].shape[0]

    def below(at):
        lt = keys[0][at] < q[0]
        for k, qk in zip(keys[1:], q[1:]):
            m = k[at]
            lt = (m < qk) | ((m == qk) & lt)
        return lt

    def step(_, bounds):
        lo_b, hi_b = bounds
        mid = (lo_b + hi_b) >> 1
        active = lo_b < hi_b
        go = active & below(jnp.minimum(mid, n - 1))
        return (jnp.where(go, mid + 1, lo_b),
                jnp.where(active & ~go, mid, hi_b))

    bounds = (jnp.zeros(q[0].shape, jnp.int32),
              jnp.full(q[0].shape, nb_live, jnp.int32))
    return jax.lax.fori_loop(0, max(1, int(n).bit_length()), step,
                             bounds)[0]


@jax.jit
def _segjoin_counts(bstack, pstack, np_live, nb_live, bhi):
    """(order, lo_pos, counts): build sort permutation, each probe row's
    first-match position in it, and its match count (0 past `np_live`).

    Both sides arrive as one stacked uint32 upload each — build planes
    in sorted order (lo[, hi_flipped], run, order), probe planes (lo,
    hi_flipped[, valid]) — so a join costs two h2d transfers however
    many key planes it needs. A 3-plane build stack has no hi plane:
    its live keys share the flipped high half `bhi`, and a probe row of
    another high half is unmatched. A probe validity plane zeroes
    invalid rows' counts: inner drops them, left emits them unmatched,
    anti keeps them, all in probe order with no compact-and-remap. Both
    plane counts are read off the stacks' shapes at trace time."""
    wide = bstack.shape[0] == 4
    keys = (bstack[0], bstack[1]) if wide else (bstack[0],)
    q = (pstack[0], pstack[1]) if wide else (pstack[0],)
    lo_pos = _lower_bound(keys, q, nb_live)
    at = jnp.minimum(lo_pos, bstack.shape[1] - 1)
    found = lo_pos < nb_live
    for k, qk in zip(keys, q):
        found = found & (k[at] == qk)
    if not wide:
        found = found & (pstack[1] == bhi)
    live = jnp.arange(pstack.shape[1], dtype=jnp.int32) < np_live
    if pstack.shape[0] == 3:
        live = live & (pstack[2] != 0)
    counts = jnp.where(live & found, bstack[-2][at].astype(jnp.int32), 0)
    return bstack[-1].astype(jnp.int32), lo_pos, counts


@functools.partial(jax.jit, static_argnames=("want_zero",))
def _segjoin_sel(counts, np_live, want_zero: bool):
    """Probe-row selection for semi (counts > 0) / anti (counts == 0),
    packed ascending, plus its device count."""
    from repro.core.bloom import flatnonzero
    n = counts.shape[0]
    live = jnp.arange(n, dtype=jnp.int32) < np_live
    ok = live & ((counts == 0) if want_zero else (counts > 0))
    return flatnonzero(ok, n), jnp.sum(ok, dtype=jnp.int32)


@jax.jit
def _segjoin_total(counts):
    return jnp.sum(counts, dtype=jnp.int32)


@jax.jit
def _segjoin_outcounts_left(counts, np_live):
    live = jnp.arange(counts.shape[0], dtype=jnp.int32) < np_live
    oc = jnp.where(live, jnp.maximum(counts, 1), 0)
    return oc, jnp.sum(oc, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("total_len", "left"))
def _segjoin_emit(order, lo_pos, counts, out_counts, total_len: int,
                  left: bool):
    """Match-pair emission: probe rows in original order, matches in
    stable build-key order (the engine output contract). Output slot t
    belongs to the first probe row whose running output count exceeds
    t (a binary search over the cumsum, not a scatter-based repeat).
    Rows past the true total are padding; the caller slices them off."""
    from repro.core.bloom import prefix_sum
    npb = counts.shape[0]
    ends = prefix_sum(out_counts)
    slot = jnp.arange(total_len, dtype=jnp.int32)
    probe_idx = jnp.minimum(
        jnp.searchsorted(ends, slot, side="right", method="scan"),
        npb - 1).astype(jnp.int32)
    within = slot - (ends - out_counts)[probe_idx]
    build_pos = lo_pos[probe_idx] + within
    build_idx = order[jnp.clip(build_pos, 0, order.shape[0] - 1)]
    if left:
        build_idx = jnp.where(counts[probe_idx] == 0, jnp.int32(-1),
                              build_idx)
    return build_idx, probe_idx


def segment_join_device(build_key: np.ndarray, probe_key: np.ndarray,
                        how: str = "inner",
                        build_valid: Optional[np.ndarray] = None,
                        probe_valid: Optional[np.ndarray] = None):
    """Device sorted-segment equi-join. Returns (build_idx, probe_idx)
    with the exact semantics of `JoinEngine.join_indices_valid` — NULL
    contract included — but as device arrays (semi/anti build_idx is a
    host -1 vector, matching the reference). One d2h scalar sync (the
    output size) per call."""
    from repro.core import device_plane as dp

    build_key = np.asarray(build_key)
    probe_key = np.asarray(probe_key)
    nb, npr = len(build_key), len(probe_key)
    bb, pb = _pow2(nb), _pow2(npr)

    # stable order by (invalid, key): valid rows by key, then NULL-key
    # and padding rows in row order — `np.argsort(kind="stable")` over
    # the valid rows, as the reference sorts them
    live = np.arange(nb) if build_valid is None \
        else np.flatnonzero(np.asarray(build_valid, bool))
    keep = np.zeros(bb, bool)
    keep[live] = True
    dead = np.flatnonzero(~keep)
    live = live[np.argsort(build_key[live], kind="stable")]
    nl = len(live)
    order = np.concatenate([live, dead])
    blo, bhi = hashing.key_halves(_pad_pow2(build_key, bb)[order])
    narrow = nl == 0 or bool((bhi[:nl] == bhi[0]).all())
    bstack = np.zeros((3 if narrow else 4, bb), np.uint32)
    bstack[0] = blo
    if not narrow:
        bstack[1] = bhi ^ _SIGN
    bstack[-2, :nl] = _run_lengths(build_key[live])
    bstack[-1] = order
    plo, phi = hashing.key_halves(_pad_pow2(probe_key, pb))
    pstack = np.empty((3 if probe_valid is not None else 2, pb),
                      np.uint32)
    pstack[0] = plo
    pstack[1] = phi ^ _SIGN
    if probe_valid is not None:
        pstack[2] = _pad_pow2(np.asarray(probe_valid, bool), pb, False)

    dp.count_segjoin(narrow)
    order, lo_pos, counts = _segjoin_counts(
        dp.to_device(bstack), dp.to_device(pstack), npr, nl,
        bhi[0] ^ _SIGN)

    if how in ("semi", "anti"):
        sel, cnt = _segjoin_sel(counts, npr, how == "anti")
        total = dp.scalar(cnt)
        return np.full(total, -1, np.int64), sel[:total]
    if how == "left":
        out_counts, cnt = _segjoin_outcounts_left(counts, npr)
    elif how == "inner":
        out_counts, cnt = counts, _segjoin_total(counts)
    else:
        raise ValueError(how)
    total = dp.scalar(cnt)
    if total == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    bidx, pidx = _segjoin_emit(order, lo_pos, counts, out_counts,
                               _pow2(total), how == "left")
    return bidx[:total], pidx[:total]
