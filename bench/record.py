"""What one run recorded: the input of every metric reader in
`bench/metrics/<metric>.py`, each of which exposes `read(run)` and
returns a number or None (nothing to read in this cell)."""
from __future__ import annotations

import dataclasses
from typing import List, Optional


@dataclasses.dataclass
class Query:
    """One query of the measured window, timed on the client."""
    template: str
    params: dict
    latency_s: float                # submit to result on the host
    report: Optional[dict]          # `ExecStats.report()`; None: failed
    rows_probed: int = 0            # transfer probes, subqueries included


@dataclasses.dataclass
class Run:
    workload: str
    setup_s: float
    window_s: float                 # first submit to last result
    queries: List[Query]
    compiles_in_window: int
    peaks: dict                     # `bench/peaks.json` entry of the chip
    trace: Optional[object] = None  # `bench.trace.Trace` (--trace 1)

    @property
    def done(self) -> List[Query]:
        return [q for q in self.queries if q.report is not None]

    def mean(self, of) -> Optional[float]:
        """Mean of `of(report)` over the completed queries."""
        vals = [of(q.report) for q in self.done]
        return sum(vals) / len(vals) if vals else None
