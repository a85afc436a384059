"""Pallas TPU kernels: open-addressing hash-table build + semi-join probe.

This is the Yannakakis baseline's primitive (paper §2.2) in TPU form: the
pointer-chasing hash map becomes a flat power-of-two table of (lo, hi)
uint32 key halves plus an occupancy lane (and, for the join map, a row
lane), linear probing bounded by the table's load factor.

Layout, as the v5e compiler accepts it: the tables stay resident in VMEM
for the whole grid in their lane-dense (cap/128, 128) view; each key's
halves and slot hash (computed by XLA ahead of the kernel) arrive in SMEM
tiles of TILE keys. Both build and lookup are serialized scalar loops —
the vector unit has no gather from VMEM — that read a slot by loading its
(1, 128) row and reducing the one lane out, and write it by a masked
row store. Tables above `VMEM_TABLE_MAX` are refused.

The cost asymmetry between this kernel and `kernels/bloom` — dependent
probes and a large VMEM-resident table vs. one 256-bit block fetch — is
exactly the β ≪ 1 asymmetry the paper's cost model builds on.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

TILE = 1024
_LANE = 128
#: VMEM all tables of one call may take (v5e: 128 MiB)
VMEM_TABLE_MAX = 96 << 20

_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * _C1
    h = h ^ (h >> 13)
    h = h * _C2
    h = h ^ (h >> 16)
    return h


def _slot_hash(lo, hi):
    return _fmix32(lo ^ _fmix32(hi))


def _as_i32(a):
    return jax.lax.bitcast_convert_type(a, jnp.int32)


def _as_u32(a):
    return jax.lax.bitcast_convert_type(a, jnp.uint32)


def _slot_reader(refs, interpret: bool):
    """`get(t, s)`: slot `s` of table `refs[t]` as a scalar. Compiled:
    load the slot's (1, 128) row and reduce its lane out. Interpret
    mode snapshots the tables as values first (a while loop whose
    condition reads a ref has no discharge rule in the interpreter)."""
    if interpret:
        vals = [r[...] for r in refs]
        return lambda t, s: vals[t][s >> 7, s & (_LANE - 1)]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)

    def get(t, s):
        row = refs[t][pl.ds(s >> 7, 1), :]
        return jnp.sum(jnp.where(lanes == (s & (_LANE - 1)), row, 0))
    return get


def _find(get, h, lo, hi, cap: int):
    """Linear probe from `h`'s home slot to the first slot that is empty
    or holds (lo, hi)."""
    def cond(s):
        return (get(0, s) != 0) & ~((get(1, s) == lo) & (get(2, s) == hi))

    return jax.lax.while_loop(cond, lambda s: (s + 1) & (cap - 1),
                              h & (cap - 1))


def _vmem_params(table_bytes: int):
    if table_bytes > VMEM_TABLE_MAX:
        raise ValueError(
            f"hash tables of {table_bytes >> 20} MiB exceed the VMEM "
            f"budget ({VMEM_TABLE_MAX >> 20} MiB)")
    return pltpu.CompilerParams(vmem_limit_bytes=table_bytes + (16 << 20))


def _key_plan(lo, hi):
    """SMEM-bound per-key scalars: slot hash, lo, hi (as int32)."""
    return _as_i32(_slot_hash(lo, hi)), _as_i32(lo), _as_i32(hi)


_SMEM_TILE = pl.BlockSpec((TILE,), lambda i: (i,), memory_space=pltpu.SMEM)


# --------------------------------------------------------------------------
# build: (klo, khi, occ, row) map; duplicate keys dedup into one slot and
# the row lane keeps the last one — the join engine only uses the map for
# duplicate-free build sides, detected from the occupancy count
# --------------------------------------------------------------------------


def _build_rows_kernel(h_ref, lo_ref, hi_ref, mask_ref, occ_ref, klo_ref,
                       khi_ref, row_ref, *, cap: int, interpret: bool):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        for r in (occ_ref, klo_ref, khi_ref, row_ref):
            r[...] = jnp.zeros_like(r)

    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)
    base = pl.program_id(0) * TILE

    def put(ref, s, v):
        r = s >> 7
        ref[pl.ds(r, 1), :] = jnp.where(lanes == (s & (_LANE - 1)), v,
                                        ref[pl.ds(r, 1), :])

    def insert(i, carry):
        get = _slot_reader((occ_ref, klo_ref, khi_ref), interpret)
        lo, hi = lo_ref[i], hi_ref[i]
        slot = _find(get, h_ref[i], lo, hi, cap)

        @pl.when(mask_ref[i] != 0)
        def _store():
            put(klo_ref, slot, lo)
            put(khi_ref, slot, hi)
            put(occ_ref, slot, 1)
            put(row_ref, slot, base + i)

        return carry

    jax.lax.fori_loop(0, TILE, insert, 0)


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def build_rows_pallas(lo, hi, mask, cap: int,
                      interpret: Optional[bool] = None):
    """(klo, khi, occ, row) uint32 [cap] tables from uint32 key halves
    [n] (n % TILE == 0); rows with mask False are not inserted."""
    n = lo.shape[0]
    assert n % TILE == 0 and cap & (cap - 1) == 0 and cap >= _LANE
    interpret = resolve_interpret(interpret)
    tables = pl.pallas_call(
        functools.partial(_build_rows_kernel, cap=cap,
                          interpret=interpret),
        grid=(n // TILE,),
        in_specs=[_SMEM_TILE] * 4,
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 4,
        out_shape=[jax.ShapeDtypeStruct((cap // _LANE, _LANE),
                                        jnp.int32)] * 4,
        compiler_params=_vmem_params(4 * cap * 4),
        interpret=interpret,
    )(*_key_plan(lo, hi), mask.astype(jnp.int32))
    occ, klo, khi, row = (_as_u32(t.reshape(cap)) for t in tables)
    return klo, khi, occ, row


def build_pallas(lo, hi, mask, cap: int, interpret: Optional[bool] = None):
    """(klo, khi, occ) membership table — the join map minus its row
    lane."""
    return build_rows_pallas(lo, hi, mask, cap, interpret=interpret)[:3]


# --------------------------------------------------------------------------
# lookup: matched build row per probe key (-1 on miss)
# --------------------------------------------------------------------------


def _lookup_kernel(h_ref, lo_ref, hi_ref, occ_ref, klo_ref, khi_ref,
                   row_ref, out_ref, *, cap: int, interpret: bool):
    get = _slot_reader((occ_ref, klo_ref, khi_ref, row_ref), interpret)

    def lookup(i, carry):
        slot = _find(get, h_ref[i], lo_ref[i], hi_ref[i], cap)
        out_ref[i] = jnp.where(get(0, slot) != 0, get(3, slot), -1)
        return carry

    jax.lax.fori_loop(0, TILE, lookup, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lookup_pallas(klo, khi, occ, row, lo, hi,
                  interpret: Optional[bool] = None):
    """int32 [n]: the `row` lane of each probe key's slot, -1 on miss."""
    cap = klo.shape[0]
    n = lo.shape[0]
    assert n % TILE == 0
    interpret = resolve_interpret(interpret)
    table = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_lookup_kernel, cap=cap, interpret=interpret),
        grid=(n // TILE,),
        in_specs=[_SMEM_TILE] * 3 + [table] * 4,
        out_specs=_SMEM_TILE,
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        compiler_params=_vmem_params(4 * cap * 4),
        interpret=interpret,
    )(*_key_plan(lo, hi),
      *(_as_i32(t).reshape(cap // _LANE, _LANE)
        for t in (occ, klo, khi, row)))


def probe_pallas(klo, khi, occ, lo, hi, interpret: Optional[bool] = None):
    """bool [n]: probe key present in the membership table. The lookup
    kernel with the occupancy lane standing in for the row lane (1 on a
    hit, -1 on a miss)."""
    return lookup_pallas(klo, khi, occ, occ, lo, hi,
                         interpret=interpret) >= 0
