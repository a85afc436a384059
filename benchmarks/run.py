"""Benchmark harness entry: one function per paper exhibit.

Prints ``name,us_per_call,derived`` CSV per the harness convention, then
each exhibit's own table. `--sf` scales TPC-H (default 0.1; the paper
uses 1.0 — pass --sf 1.0 for the full-size run).

``--json PATH`` additionally writes a machine-readable benchmark file
(per-strategy per-query seconds, geomean speedups, kernel-bench rows,
and a per-backend Q5 transfer-phase split) so the perf trajectory is
tracked across PRs — see BENCH_tpch.json."""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

# runnable as `python benchmarks/run.py` from the repo root: make the
# `benchmarks` package importable regardless of how we were invoked
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def q5_transfer_split(sf: float, backends=("numpy", "jax")):
    """Transfer-phase wall time on Q5 per engine backend (median of 5
    warm runs) — the engine hot path the perf gate watches. Backends
    are interleaved round-robin so a co-tenant load burst lands on all
    of them and their *ratios* stay drift-immune."""
    from benchmarks.common import gc_fence, run_query
    for backend in backends:
        run_query(sf, 5, "pred-trans", backend=backend)   # warm caches
    ts = {backend: [] for backend in backends}
    with gc_fence():
        for _ in range(5):
            for backend in backends:
                _, stats = run_query(sf, 5, "pred-trans", warm=0,
                                     backend=backend)
                ts[backend].append(stats.transfer.seconds)
            gc.collect()
    return {backend: sorted(v)[len(v) // 2] for backend, v in ts.items()}


def measure_paired_speedups(sf: float, repeat: int = 5):
    """Per-query pred-trans speedup via interleaved paired runs — the
    estimator `--check` gates on, recorded into the baseline file by
    `--json` so gate and baseline share one measurement protocol.

    Pairing makes each ratio drift-immune (a load burst hits both
    sides); the *median* over `repeat` pairs discards the outlier pairs
    a burst lands between. Seconds keep the minimum (stable envelope)."""
    from benchmarks.common import gc_fence, run_query
    from repro.tpch import QUERIES
    out = {}
    for qn in sorted(QUERIES):
        run_query(sf, qn, "no-pred-trans", warm=0)        # warm
        run_query(sf, qn, "pred-trans", warm=0)
        ratios, pts = [], []
        with gc_fence():
            for _ in range(repeat):
                t_npt = run_query(sf, qn, "no-pred-trans",
                                  warm=0)[1].total_seconds
                t_pt = run_query(sf, qn, "pred-trans",
                                 warm=0)[1].total_seconds
                pts.append(t_pt)
                ratios.append(t_npt / t_pt)
                gc.collect()
        ratios.sort()
        out[f"Q{qn}"] = {"pred_trans_seconds": min(pts),
                         "speedup": ratios[len(ratios) // 2]}
    return out


def measure_adaptive(sf: float, repeat: int = 7):
    """Paired per-query measurement for the adaptive scheduler: each
    rep interleaves no-pred-trans, pred-trans and pred-trans-adaptive,
    so both ratios — adaptive speedup over baseline and the
    adaptive/pred-trans regression ratio `--check` gates on — are
    drift-immune. Medians over `repeat` pairs (7: the skip-everything
    queries sit within a few percent of baseline, where a 5-pair
    median still flips on one co-tenant burst); seconds keep the
    minimum (stable envelope)."""
    from benchmarks.common import gc_fence, run_query
    from repro.tpch import QUERIES
    out = {}
    for qn in sorted(QUERIES):
        for s in ("no-pred-trans", "pred-trans", "pred-trans-adaptive"):
            run_query(sf, qn, s, warm=0)                  # warm
        sp, ratio, secs = [], [], []
        with gc_fence():
            for _ in range(repeat):
                t_npt = run_query(sf, qn, "no-pred-trans",
                                  warm=0)[1].total_seconds
                t_pt = run_query(sf, qn, "pred-trans",
                                 warm=0)[1].total_seconds
                t_ad = run_query(sf, qn, "pred-trans-adaptive",
                                 warm=0)[1].total_seconds
                secs.append(t_ad)
                sp.append(t_npt / t_ad)
                ratio.append(t_ad / t_pt)
                gc.collect()
        sp.sort()
        ratio.sort()
        out[f"Q{qn}"] = {"adaptive_seconds": min(secs),
                         "speedup": sp[len(sp) // 2],
                         "vs_pred_trans": ratio[len(ratio) // 2]}
    return out


def adaptive_decisions(sf: float):
    """One adaptive run per query through the unified
    `ExecStats.report()` surface: per-edge scheduling decisions
    (estimated vs actual selectivity with q-error, skip/apply/prune/
    min-max-cut) plus the runtime join-order record — the
    decision-quality exhibits BENCH_tpch.json tracks."""
    from benchmarks.common import run_query
    from repro.tpch import QUERIES

    def rnd(e: dict) -> dict:
        return {k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in e.items()}

    dec, qerr, jorder = {}, {}, {}
    for qn in sorted(QUERIES):
        _, stats = run_query(sf, qn, "pred-trans-adaptive", warm=0)
        rep = stats.report()
        tr = rep["transfer"] or {}
        q = f"Q{qn}"
        dec[q] = {"decisions": tr.get("decisions"),
                  "passes_run": tr.get("passes_run"),
                  "edges": [rnd(e) for e in rep["edges"]]}
        qerr[q] = rnd(rep["qerror"])
        jorder[q] = {"reordered": rep["reordered"],
                     "regions": rep["join_order"]}
    return {"decisions": dec, "qerror": qerr, "join_order": jorder}


def device_round_trips(sf: float):
    """Host<->device round trips per query: the device-resident data
    plane (DESIGN.md §15, `ExecConfig.device="on"`) vs the legacy
    per-op path (`"off"`), both on the jax engines and both counted
    through `repro.core.device_plane`, so the comparison is symmetric.
    A round trip here is any boundary crossing (h2d + d2h syncs) — the
    serialized-dependency count that bounds dispatch latency. The
    counts are structural (a
    function of the plan and the survivor cardinalities, not the
    clock), so the on<off gate is drift-immune by construction and
    needs no baseline. Each query's on/off results are md5-compared
    first — a round-trip win backed by wrong rows is worthless."""
    from benchmarks.common import catalog
    from repro.core.transfer import make_strategy
    from repro.relational import ExecConfig, Executor
    from repro.relational.table import table_digest
    from repro.tpch import QUERIES, build_query
    cat = catalog(sf)
    per = {}
    tot = {"on": 0, "off": 0}
    for qn in sorted(QUERIES):
        row, digest = {}, {}
        for mode in ("on", "off"):
            cfg = ExecConfig(
                strategy=make_strategy("pred-trans", backend="jax",
                                       device_resident=(mode == "on")),
                join_backend="jax", device=mode)
            res, stats = Executor(cat, cfg).execute(
                build_query(qn, sf=sf))
            digest[mode] = table_digest(res)
            row[mode] = stats.report()["device"]["round_trips"]
            tot[mode] += row[mode]
        if digest["on"] != digest["off"]:
            raise AssertionError(
                f"Q{qn}: device on/off results diverged")
        per[f"Q{qn}"] = row
    print(f"{'query':>6} {'rt on':>6} {'rt off':>7}")
    for q, r in per.items():
        print(f"{q:>6} {r['on']:>6} {r['off']:>7}")
    print(f"{'total':>6} {tot['on']:>6} {tot['off']:>7}")
    return {"round_trips_on": tot["on"], "round_trips_off": tot["off"],
            "per_query": per}


def run_check(sf: float, baseline_path: str, rel_tol: float = 0.10,
              gross_tol: float = 0.75, repeat: int = 5) -> int:
    """Regression gate vs the committed BENCH_tpch.json.

    Wall-clock on a shared box drifts 20-35% between runs, so raw
    seconds cannot carry a 10% gate. The 10% tolerance is applied to
    *machine-drift-immune ratios* — per-query pred-trans speedup over
    the simultaneously re-measured no-pred-trans, their geomean, and
    the Q5 jax/numpy transfer ratio (with its hard 5x ceiling) — while
    raw per-query seconds keep a gross-blowup guard (`gross_tol`) that
    still catches complexity regressions. Each query is measured
    `repeat` times and gated on the minimum (the stable envelope)."""
    from benchmarks.common import run_query
    from repro.tpch import QUERIES
    with open(baseline_path) as f:
        baseline = json.load(f)
    if baseline.get("sf") != sf:
        print(f"check: baseline {baseline_path} is sf={baseline.get('sf')}"
              f", run is sf={sf} — nothing to compare", file=sys.stderr)
        return 2

    failures = []

    def gate(name, new, old, tol, higher_is_better=False, slack=0.0):
        if old is None or new is None:
            return
        if higher_is_better:
            bad = new < old * (1 - tol) - slack
        else:
            bad = new > old * (1 + tol) + slack
        tag = "FAIL" if bad else "ok  "
        print(f"check: {tag} {name}: {new:.4f} vs baseline {old:.4f}",
              file=sys.stderr)
        if bad:
            failures.append(name)

    measured = measure_paired_speedups(sf, repeat=repeat)
    base_paired = baseline.get("check_paired_speedup", {})
    base_rows = {r["query"]: r
                 for r in baseline.get("tpch", {})
                 .get("per_query_seconds", [])}
    speedups, base_speedups = [], []
    for qn in sorted(QUERIES):
        q = f"Q{qn}"
        m = measured.get(q)
        b = base_paired.get(q)
        if m is None:
            continue
        if b is None:                    # old baseline: unpaired numbers
            br = base_rows.get(q, {})
            b = {"speedup": br.get("speedup_pred-trans"),
                 "pred_trans_seconds": br.get("pred-trans")}
        pt, ratio = m["pred_trans_seconds"], m["speedup"]
        if b.get("speedup"):
            # geomeans must aggregate the same query set on both sides
            speedups.append(ratio)
            base_speedups.append(b["speedup"])
        # Per-query gates get 20 chances per run to flake and a 5-pair
        # median window can sit entirely inside one co-tenant load
        # burst (observed ~30% median swings on a healthy build), so
        # they act as blowup guards at ~3.5x the tolerance — a single
        # query losing >1.5x of its speedup still trips them — while
        # the 10% precision gate lives on the 20-query geomean below,
        # which averages bursts out. Jitter slack scales with 1/time
        # (~2ms scheduler noise is a big ratio swing on a 10ms query).
        gate(f"{q} pred-trans speedup", ratio, b.get("speedup"),
             3.5 * rel_tol, higher_is_better=True,
             slack=0.05 + 0.002 / pt)
        gate(f"{q} pred-trans seconds (gross)", pt,
             b.get("pred_trans_seconds"), gross_tol, slack=0.05)
    if speedups and base_speedups:
        import numpy as np
        gate("pred-trans geomean speedup",
             float(np.exp(np.mean(np.log(speedups)))),
             float(np.exp(np.mean(np.log(base_speedups)))),
             rel_tol, higher_is_better=True)
    # adaptive scheduler gate: pred-trans-adaptive may never regress
    # >10% against pred-trans on any query. Both sides are re-measured
    # interleaved in the same window, so the ratio is drift-immune and
    # needs no baseline — the committed numbers only anchor the
    # adaptive *speedup* geomean below. Jitter slack scales with 1/time
    # like the per-query speedup gates above.
    adaptive = measure_adaptive(sf)
    base_adaptive = baseline.get("check_adaptive", {})
    ad_sp, base_ad_sp = [], []
    for q, m in sorted(adaptive.items()):
        gate(f"{q} adaptive/pred-trans ratio", m["vs_pred_trans"],
             1.0, rel_tol, slack=0.05 + 0.002 / m["adaptive_seconds"])
        b = base_adaptive.get(q, {})
        if b.get("speedup"):
            ad_sp.append(m["speedup"])
            base_ad_sp.append(b["speedup"])
    if ad_sp and base_ad_sp:
        import numpy as np
        gate("pred-trans-adaptive geomean speedup",
             float(np.exp(np.mean(np.log(ad_sp)))),
             float(np.exp(np.mean(np.log(base_ad_sp)))),
             rel_tol, higher_is_better=True)

    # reorder-robustness gate (DESIGN §14): on the widest join graphs,
    # the runtime order must sit within 10% of the *best* static order
    # among the plan's own and >=3 adversarial permutations. Every
    # order runs interleaved in the same rep window, so the gated
    # ratio is drift-immune and needs no baseline; jitter slack scales
    # with 1/time like the other per-query gates.
    from benchmarks import reorder_bench
    print("\n===== reorder robustness (gate) =====", file=sys.stderr)
    # median-of-9 reps regardless of --repeat: the gated number is the
    # worst per-opponent median paired ratio, and each median needs
    # enough reps to be tight on a noisy box. The extra slack absorbs
    # the runtime leg's fixed decision overhead (ndistinct + subset DP,
    # ~3-8% of these 30-140ms queries) on top of the usual jitter.
    rb = reorder_bench.main(sf, repeat=max(repeat, 9))
    for q, r in sorted(rb["queries"].items()):
        gate(f"{q} runtime/best-static order ratio",
             r["runtime_over_best_static"], 1.0, rel_tol,
             slack=0.08 + 0.002 / r["best_static_seconds"])

    # serving gate: cold and warm passes share one measurement window
    # (paired), so the warm/cold throughput ratio is drift-immune. The
    # 1.3x floor is the serving-layer acceptance contract at
    # concurrency 4; the baseline ratio adds the usual 10% band on top.
    from benchmarks import serving_bench
    serving = serving_bench.main(sf, concurrency=(4,), reps=2, pairs=3)
    srow = serving["concurrency"]["4"]
    base_srow = baseline.get("serving", {}).get("concurrency",
                                                {}).get("4", {})
    gate("serving warm/cold throughput (hard 1.3x floor)",
         srow["warm_over_cold"], 1.3, 0.0, higher_is_better=True)
    gate("serving warm/cold throughput", srow["warm_over_cold"],
         base_srow.get("warm_over_cold"), rel_tol,
         higher_is_better=True)
    if srow["slot_cache_hit_rate"] <= 0:
        print("check: FAIL serving slot-cache hit rate is zero",
              file=sys.stderr)
        failures.append("serving slot-cache hits")

    # device data-plane gate (DESIGN §15): with the fused
    # transfer->join path on, the 20-query aggregate of host<->device
    # round trips must beat the legacy per-op path, bit-exactness
    # included. Counts, not clocks — drift-immune, no baseline needed.
    # Runs on the small catalog regardless of --sf: round trips scale
    # with plan shape, not data size.
    print("\n===== device data plane (gate) =====", file=sys.stderr)
    dev = device_round_trips(0.01)
    on_rt, off_rt = dev["round_trips_on"], dev["round_trips_off"]
    tag = "FAIL" if on_rt >= off_rt else "ok  "
    print(f"check: {tag} device round trips on={on_rt} < off={off_rt}",
          file=sys.stderr)
    if on_rt >= off_rt:
        failures.append("device round trips")

    # chaos gate: correctness, not timing — every fault point must fire,
    # degrade (or self-heal), and leave zero wrong results. Runs on the
    # small catalog regardless of --sf: the gate checks ladder
    # mechanics, which don't scale with data size.
    from benchmarks import chaos_bench
    print("\n===== chaos (gate) =====", file=sys.stderr)
    if chaos_bench.smoke(0.01) != 0:
        failures.append("chaos fault-injection suite")

    # overload gate (DESIGN §16): shedding, typed rejections, bounded
    # accepted p99, warm restart — correctness + contract, small
    # catalog regardless of --sf
    from benchmarks import overload_bench
    print("\n===== overload (gate) =====", file=sys.stderr)
    if overload_bench.smoke(0.01) != 0:
        failures.append("overload-control suite")

    split = q5_transfer_split(sf)
    base_split = baseline.get("q5_transfer_seconds", {})
    if "numpy" in split and "jax" in split:
        # the two splits are measured in the same window, so their
        # ratio is drift-immune; the 5x ceiling is the hard engine
        # contract and applies even when the baseline lacks the splits
        ratio = split["jax"] / split["numpy"]
        allowed = 5.0
        if base_split.get("numpy") and base_split.get("jax"):
            allowed = max(
                base_split["jax"] / base_split["numpy"] * (1 + rel_tol),
                allowed)
        gate("q5 transfer jax/numpy ratio", ratio, allowed, 0.0)

    if failures:
        print(f"check: {len(failures)} regression(s): "
              + ", ".join(failures), file=sys.stderr)
        return 1
    print("check: all tracked numbers within tolerance", file=sys.stderr)
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.1)
    ap.add_argument("--kernel-n", type=int, default=1_000_000)
    ap.add_argument("--only", default=None,
                    help="comma-separated exhibit names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable results (BENCH_tpch.json)")
    ap.add_argument("--check", action="store_true",
                    help="regression gate: re-measure the TPC-H sweep and "
                         "fail on >10%% regression vs the committed "
                         "baseline (--json PATH, default BENCH_tpch.json)")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    if args.check:
        sys.exit(run_check(args.sf, args.json or "BENCH_tpch.json"))

    from benchmarks import (chaos_bench, curation_bench,
                            distributed_transfer, figure2_tpch,
                            figure3_breakdown, figure4_robustness,
                            kernel_bench, overload_bench,
                            reorder_bench, serving_bench,
                            table1_q5_sizes)

    exhibits = {
        "figure2_tpch": lambda: figure2_tpch.main(args.sf),
        "table1_q5_sizes": lambda: table1_q5_sizes.main(args.sf),
        "figure3_breakdown": lambda: figure3_breakdown.main(args.sf),
        "figure4_robustness": lambda: figure4_robustness.main(args.sf),
        "kernel_bench": lambda: kernel_bench.main(args.kernel_n),
        "distributed_transfer": distributed_transfer.main,
        "distributed_join": lambda: distributed_transfer
        .distributed_join_main(args.sf),
        "curation_bench": lambda: curation_bench.main(
            max(int(args.sf * 1_000_000), 20_000)),
        "serving": lambda: serving_bench.main(args.sf),
        "chaos": lambda: chaos_bench.main(args.sf),
        "overload": lambda: overload_bench.main(args.sf),
        "reorder": lambda: reorder_bench.main(args.sf),
        "device": lambda: device_round_trips(args.sf),
    }
    if args.only:
        names = args.only.split(",")
        exhibits = {n: exhibits[n] for n in names}

    print("name,us_per_call,derived")
    timings = {}
    results = {}
    for name, fn in exhibits.items():
        print(f"\n===== {name} =====", file=sys.stderr)
        t0 = time.perf_counter()
        results[name] = fn()
        timings[name] = (time.perf_counter() - t0) * 1e6
    print("\nname,us_per_call,derived")
    for name, us in timings.items():
        derived = ""
        if name == "figure2_tpch":
            derived = (f"geomean_pred_trans="
                       f"{results[name][1]['pred-trans']['geomean_speedup']:.2f}x")
        print(f"{name},{us:.0f},{derived}")

    if args.json:
        # merge into an existing same-sf file: keys this run didn't
        # produce (e.g. the recorded seed baseline) survive
        # regeneration. A different --sf starts fresh — every number
        # in the file shares one provenance.
        doc = {}
        if os.path.exists(args.json):
            try:
                with open(args.json) as f:
                    prev = json.load(f)
                if prev.get("sf") == args.sf:
                    doc = prev
            except (OSError, ValueError):
                pass
        doc["sf"] = args.sf
        if "figure2_tpch" in results:
            rows, summary = results["figure2_tpch"]
            doc["tpch"] = {"per_query_seconds": rows,
                           "summary": summary}
            # TPC-H already scoped by this run, so the Q5 engine split
            # (the perf-gate number) is re-measured too
            print("\n===== q5_transfer_split =====", file=sys.stderr)
            doc["q5_transfer_seconds"] = q5_transfer_split(args.sf)
            # same paired estimator --check gates on (protocol match)
            print("\n===== check_paired_speedup =====", file=sys.stderr)
            doc["check_paired_speedup"] = measure_paired_speedups(args.sf)
            print("\n===== check_adaptive =====", file=sys.stderr)
            doc["check_adaptive"] = measure_adaptive(args.sf)
            print("\n===== adaptive_decisions =====", file=sys.stderr)
            ad = adaptive_decisions(args.sf)
            doc["adaptive_decisions"] = ad["decisions"]
            doc["qerror"] = ad["qerror"]
            doc["join_order"] = ad["join_order"]
        if "kernel_bench" in results:
            kb = results["kernel_bench"]
            doc["kernel_bench_ns_per_row"] = dict(kb["rows"])
            doc["transfer_cost_calibration"] = kb["calibration"]
            doc["join_crossover"] = kb["join_crossover"]
        if "distributed_join" in results:
            doc["distributed_join"] = results["distributed_join"]
        if "serving" in results:
            doc["serving"] = results["serving"]
        if "chaos" in results:
            doc["chaos"] = results["chaos"]
        if "overload" in results:
            doc["overload"] = results["overload"]
        if "reorder" in results:
            doc["reorder"] = results["reorder"]
        if "device" in results:
            doc["device_plane"] = results["device"]
        tmp = args.json + ".tmp"
        with open(tmp, "w") as f:       # atomic: a crash mid-dump must
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, args.json)      # not truncate the baseline
        print(f"wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
