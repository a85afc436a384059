"""Public jit'd wrappers for the bloom Pallas kernels.

Handles host-side key splitting and TILE padding; interpret mode is
resolved by `repro.kernels.resolve_interpret`.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import hashing
from repro.core.bloom import DEFAULT_BITS_PER_KEY, DEFAULT_K, blocks_for
from repro.kernels.bloom import bloom as _k


def _pad_to_tile(a: np.ndarray, fill=0) -> np.ndarray:
    n = len(a)
    m = ((n + _k.TILE - 1) // _k.TILE) * _k.TILE
    if m == n:
        return a
    out = np.full(m, fill, dtype=a.dtype)
    out[:n] = a
    return out


def bloom_build(keys: np.ndarray, mask: Optional[np.ndarray] = None,
                bits_per_key: int = DEFAULT_BITS_PER_KEY,
                k: int = DEFAULT_K,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """Build filter words (uint32 [nblocks, 8]) from int64 keys."""
    keys = np.asarray(keys)
    if mask is None:
        mask = np.ones(len(keys), bool)
    n_live = int(np.asarray(mask).sum())
    nblocks = blocks_for(max(n_live, 1), bits_per_key)
    lo, hi = hashing.key_halves(_pad_to_tile(keys))
    m = _pad_to_tile(np.asarray(mask, bool), False)
    return _k.build_pallas(jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(m),
                           nblocks, k=k, interpret=interpret)


def bloom_probe(words: jnp.ndarray, keys: np.ndarray,
                k: int = DEFAULT_K,
                interpret: Optional[bool] = None) -> np.ndarray:
    keys = np.asarray(keys)
    lo, hi = hashing.key_halves(_pad_to_tile(keys))
    out = _k.probe_pallas(words, jnp.asarray(lo), jnp.asarray(hi), k=k,
                          interpret=interpret)
    return np.asarray(out)[: len(keys)]


def bloom_transfer(in_words: jnp.ndarray,
                   in_keys: np.ndarray, out_keys: np.ndarray,
                   mask: Optional[np.ndarray] = None,
                   bits_per_key: int = DEFAULT_BITS_PER_KEY,
                   k: int = DEFAULT_K,
                   interpret: Optional[bool] = None
                   ) -> Tuple[np.ndarray, jnp.ndarray]:
    """Fused filter transformation: returns (survivor_mask, out_words)."""
    in_keys, out_keys = np.asarray(in_keys), np.asarray(out_keys)
    assert len(in_keys) == len(out_keys)
    if mask is None:
        mask = np.ones(len(in_keys), bool)
    n_live = int(np.asarray(mask).sum())
    nblocks_out = blocks_for(max(n_live, 1), bits_per_key)
    ilo, ihi = hashing.key_halves(_pad_to_tile(in_keys))
    olo, ohi = hashing.key_halves(_pad_to_tile(out_keys))
    m = _pad_to_tile(np.asarray(mask, bool), False)
    ok, outw = _k.transfer_pallas(
        in_words, jnp.asarray(ilo), jnp.asarray(ihi), jnp.asarray(olo),
        jnp.asarray(ohi), jnp.asarray(m), nblocks_out, k=k,
        interpret=interpret)
    return np.asarray(ok)[: len(in_keys)], outw
