"""The comparison that decides a run's `correct`.

Each compared answer of the window is set beside the plain reference's
(`bench.reference`) for the same template and parameters. Two numbers
come of it, each held to a limit that `BENCHMARK`'s traffic file names:

* `rows_differing` — rows whose exact columns (keys, dates, counts,
  names) differ, plus the difference in row count. Exact: limit 0.
* `value_rel_gap` — the widest relative gap of a money column,
  |program - reference| / max(|reference|, 1), NaN read as 1. Both sides sum float64
  values in their own order, so they differ by rounding; a reference
  computed in float32 (the control) differs by far more.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from bench.reference import Strings


def plain_tables(catalog) -> Dict[str, Dict[str, object]]:
    """The catalog as plain columns: numeric columns as their ndarray,
    string columns as `Strings(codes, vocabulary)`."""
    out = {}
    for name, table in catalog.items():
        cols = {}
        for c in table.names:
            col = table[c]
            cols[c] = (col.data if col.dictionary is None
                       else Strings(col.data, np.asarray(col.dictionary)))
        out[name] = cols
    return out


def compare(got: Dict[str, np.ndarray],
            want: Dict[str, np.ndarray]) -> Tuple[int, float]:
    """(rows_differing, value_rel_gap) of one answer against the
    reference's. `got` is the program's result as decoded columns."""
    if list(got) != list(want):
        return max(len(next(iter(want.values()), [])), 1), 1.0
    n_got = len(next(iter(got.values()), []))
    n_want = len(next(iter(want.values()), []))
    n = min(n_got, n_want)
    bad = np.zeros(n, bool)
    gap = 0.0
    for name, w in want.items():
        g = np.asarray(got[name])[:n]
        w = np.asarray(w)[:n]
        if w.dtype.kind == "f":
            w = w.astype(np.float64)
            g = g.astype(np.float64)
            if n:
                rel = np.abs(g - w) / np.maximum(np.abs(w), 1.0)
                gap = max(gap, float(np.nan_to_num(rel, nan=1.0).max()))
        else:
            bad |= g != w
    return int(bad.sum()) + abs(n_got - n_want), gap
