"""The plain reference: TPC-H answers in straightforward numpy.

It shares no code with the system under test (nothing from `repro`): it
reads the catalog as plain columns — a numeric column is an ndarray, a
string column a `Strings` of dictionary codes and vocabulary — and
computes each query the obvious way: boolean masks, joins by sorting
and binary search, group-by by `np.unique`, sums by `np.bincount`.

`Reference(tables, dtype)` answers `ref.answer(template, params)`.
`dtype=np.float32` gives the control: the same plain reference with
every money expression and sum computed one precision below the
configuration's float64, which the comparison must refuse.
Parameter-independent parts (per-order supplier counts, per-part mean
quantity, order years) are computed once per `Reference` and reused.
"""
from __future__ import annotations

import datetime
import importlib
from typing import Dict, NamedTuple

import numpy as np

_EPOCH = datetime.date(1970, 1, 1).toordinal()


class Strings(NamedTuple):
    """A dictionary-encoded string column: `vocab[codes]` are its values."""
    codes: np.ndarray
    vocab: np.ndarray

    def decode(self) -> np.ndarray:
        return self.vocab[self.codes]


def epoch_day(s: str) -> int:
    """'YYYY-MM-DD' -> days since 1970-01-01."""
    y, m, d = map(int, s.split("-"))
    return datetime.date(y, m, d).toordinal() - _EPOCH


def where(col: Strings, test) -> np.ndarray:
    """Row mask of a string column whose value passes `test(str)`."""
    ok = np.array([bool(test(str(v))) for v in col.vocab], bool)
    return ok[col.codes]


def lookup(build_keys: np.ndarray, probe_keys: np.ndarray) -> np.ndarray:
    """For each probe key, the row of the equal key in `build_keys`
    (unique), or -1."""
    if len(build_keys) == 0:
        return np.full(len(probe_keys), -1, np.int64)
    order = np.argsort(build_keys, kind="stable")
    srt = build_keys[order]
    pos = np.minimum(np.searchsorted(srt, probe_keys), len(srt) - 1)
    hit = srt[pos] == probe_keys
    return np.where(hit, order[pos], -1)


def join(build_keys: np.ndarray, probe_keys: np.ndarray):
    """Equi-join of two key columns, duplicates on both sides kept:
    (probe rows, build rows) of every matching pair, in probe order."""
    order = np.argsort(build_keys, kind="stable")
    srt = build_keys[order]
    lo = np.searchsorted(srt, probe_keys, side="left")
    hi = np.searchsorted(srt, probe_keys, side="right")
    n = hi - lo
    probe = np.repeat(np.arange(len(probe_keys)), n)
    offset = np.arange(len(probe)) - np.repeat(np.cumsum(n) - n, n)
    return probe, order[np.repeat(lo, n) + offset]


def pair_key(a: np.ndarray, b: np.ndarray, span: int) -> np.ndarray:
    """One int64 key for a pair of non-negative keys, `b < span`."""
    return a.astype(np.int64) * span + b


def group_sum(inverse: np.ndarray, values: np.ndarray, n: int,
              dtype) -> np.ndarray:
    """Per-group sums in `dtype`: float64 by `np.bincount`, a lower
    precision by sequential addition in that precision."""
    if np.dtype(dtype) == np.float64:
        return np.bincount(inverse, weights=values.astype(np.float64),
                           minlength=n)
    order = np.argsort(inverse, kind="stable")
    counts = np.bincount(inverse, minlength=n)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    vals = values[order].astype(dtype)
    out = np.zeros(n, dtype)
    nz = counts > 0
    if vals.size:
        out[nz] = np.add.reduceat(vals, starts[nz], dtype=dtype)
    return out


class Reference:
    """Answers of the query templates in `bench/reference/q<N>.py` over
    one catalog, with money arithmetic in `dtype`."""

    def __init__(self, tables: Dict[str, Dict[str, object]],
                 dtype=np.float64):
        self.t = tables
        self.dtype = np.dtype(dtype)
        self._memo: Dict[str, object] = {}

    def memo(self, key: str, make):
        """A parameter-independent intermediate, computed once."""
        if key not in self._memo:
            self._memo[key] = make()
        return self._memo[key]

    def money(self, table: str, col: str) -> np.ndarray:
        return self.t[table][col].astype(self.dtype)

    def answer(self, template: str, params: dict) -> Dict[str, np.ndarray]:
        mod = importlib.import_module(f"bench.reference.{template}")
        return mod.answer(self, params)
