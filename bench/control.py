#!/usr/bin/env python3
"""The control of a cell's comparison, at the cell's own size.

    python3 bench/control.py --workload sf1-adhoc --seeds 11 12 13

Generate the cell's database, then for each seed answer every query of
the cell's parameter set, in that seed's order, with the plain
reference in float64 and with the control — the same reference computed
in float32, one precision below the configuration's — and compare the
control's answers as a run compares the program's. The comparison has
to refuse the control: its smallest worst gap over the seeds is the
upper reading the limit `value_rel_gap` is set below. The benchmark's
runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.correct import compare, plain_tables
    from bench.reference import Reference
    from bench.run import Stream, resolve
    from repro.tpch import generate
    _, _, config, traffic = resolve(args.workload)
    tables = plain_tables(generate(sf=config["sf"], seed=config["data_seed"]))
    ref, ctl = Reference(tables), Reference(tables, np.float32)
    for seed in args.seeds:
        t0 = time.perf_counter()
        stream = Stream(traffic, seed)
        rows, gap, n = 0, 0.0, 0
        for i in range(stream.cycles or 1):
            for t, p, _ in stream.cycle(i):
                r, g = compare(ctl.answer(t, p), ref.answer(t, p))
                rows, gap, n = rows + r, max(gap, g), n + 1
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "answers": n, "rows_differing": rows,
                          "value_rel_gap": gap,
                          "limit": config["limits"]["value_rel_gap"],
                          "seconds": time.perf_counter() - t0}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = ROOT      # find bench's modules as `bench.*` only
    sys.exit(main())
