#!/usr/bin/env python3
"""One run of one benchmark cell of the predicate-transfer engine.

    python3 bench/run.py --workload sf1-adhoc --seed 7 --seconds 51 --trace 0

The cell (`BENCHMARK.json` `workloads`) names a configuration,
`bench/configs/<config>.json` (scale factor, the database's seed, server
settings, limits of the comparison), and a traffic mix,
`bench/traffic/<traffic>.json` (templates, their order, the parameter
set, the warm-up). Query templates are `bench/queries/<template>.py`,
their plain reference `bench/reference/<template>.py`, and each metric
is read by `bench/metrics/<metric>.py`. Nothing here names a cell.

The run, in one process: generate the configuration's TPC-H database;
warm up — rehearse every query the window may send on a server of its
own (`rehearse`), or send the first cycles to the measured server
(`prime`); then one closed-loop client sends the stream, in the order
`--seed` gives it, to the measured `repro.serve.QueryServer`, waits for
each answer and times it, until the first whole cycle of the stream
that ends at least `--seconds` after the first submit, or the end of
the parameter set. After the window: read the device's peak memory,
stop the server, and compare every answer of the window with the plain
reference. With `--trace 1` the window runs under the JAX profiler and
the per-layer metrics are read from the reports and the trace.

The last line of standard output is the JSON result. The run fails —
non-zero exit, no result — when JAX finds no TPU or fewer chips than
the cell asks for, when the chip is not in `bench/peaks.json`, or when
an engine runs Pallas in interpret mode or routes builds or compaction
to the host.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """The run cannot be measured: no result is printed."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    path = os.path.join(ROOT, *parts)
    if not os.path.isfile(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileTally:
    """Backend compilations (compile-cache loads included), from JAX's
    compile-duration events, in all and per jitted function."""

    def __init__(self):
        self.count, self.seconds = 0, 0.0
        self.per_fun: Dict[str, List[float]] = {}

    def __call__(self, event: str, secs: float, fun_name: str = "?",
                 **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs
            n_s = self.per_fun.setdefault(fun_name, [0, 0.0])
            n_s[0] += 1
            n_s[1] += secs

    def top(self, n: int = 8) -> str:
        ranked = sorted(self.per_fun.items(), key=lambda kv: -kv[1][1])
        return ", ".join(f"{f} {c}x {s:.1f} s" for f, (c, s) in ranked[:n])


def steady_allocator() -> None:
    """Fix glibc malloc's thresholds for the process. Left dynamic, they
    follow the history of allocations: on a TPU v5e host the order one
    seed gave the stream made that run's host work up to 17% slower
    throughout (Q5 2.55 s against 2.17 s, on every draw). Large arrays
    come from the heap below 32 MiB, and the heap is not trimmed back
    after each query."""
    import ctypes
    import ctypes.util
    libc = ctypes.CDLL(ctypes.util.find_library("c"))
    for param, value in ((-3, 32 << 20),        # M_MMAP_THRESHOLD
                         (-1, (1 << 31) - 1),   # M_TRIM_THRESHOLD
                         (-2, 256 << 20)):      # M_TOP_PAD
        if libc.mallopt(param, value) != 1:
            raise BenchError(f"mallopt({param}, {value}) refused")


def gc_fence() -> None:
    """Collect set-up's garbage before the window, and move what
    survives out of the collector's sight: the window's collections
    then scan only what the window allocates. The collector stays on —
    over a window of tens of seconds it is part of the served system."""
    gc.collect()
    gc.freeze()


def engines_off_device() -> List[str]:
    """Cached Bloom / join engines that run Pallas in interpret mode or
    route filter builds or survivor compaction through the host."""
    from repro.core import engine_bloom, engine_join
    engines = list(engine_bloom._ENGINES.values()) \
        + list(engine_join._ENGINES.values())
    return [f"{type(e).__name__}({e.backend}).{flag}" for e in engines
            for flag in ("interpret", "host_build", "host_compact")
            if getattr(e, flag, False)]


class Stream:
    """The closed-loop client's query stream: templates in the traffic's
    order, cycle after cycle.

    The cell's parameter set is drawn once from the traffic's
    `set_seed`, without replacement from each template's qgen domain:
    `cycles` draws per template for `fresh` parameters (no draw is sent
    twice in a run), one for `fixed` (the same draw every cycle). Every
    seed runs the same set, so every seed does the same work and finds
    the same programs in the compile cache; `--seed` orders each
    template's draws."""

    def __init__(self, traffic: dict, seed: int):
        self.templates = list(traffic["templates"])
        self.mode = traffic["params"]
        if self.mode not in ("fresh", "fixed"):
            raise BenchError(f"unknown params mode {self.mode!r}")
        self.cycles = traffic["cycles"] if self.mode == "fresh" else None
        pick = np.random.default_rng(traffic["set_seed"])
        order = np.random.default_rng([seed, 1])
        self.mods = {t: importlib.import_module(f"bench.queries.{t}")
                     for t in self.templates}
        self.draws: Dict[str, list] = {}
        for t in self.templates:
            dom = self.mods[t].domain()
            chosen = pick.choice(len(dom), self.cycles or 1, replace=False)
            self.draws[t] = [dom[i] for i in order.permutation(chosen)]

    def cycle(self, i: int):
        """Cycle `i` of the stream: [(template, params, plan)]."""
        if self.cycles is not None and i >= self.cycles:
            raise IndexError(f"the stream has {self.cycles} cycles")
        out = []
        for t in self.templates:
            p = self.draws[t][i % len(self.draws[t])]
            out.append((t, p, self.mods[t].plan(p)))
        return out


def rows_probed(stats) -> int:
    """Keys the transfer phase probed, subqueries included; 0 for a
    query whose transfer was replayed from the slot cache."""
    tr = stats.transfer
    n = 0 if tr is None or tr.from_cache else int(tr.rows_probed)
    return n + sum(rows_probed(s) for s in stats.subqueries)


def run_query(srv, template, params, plan):
    """Submit one query and wait for its answer: (Query, answer), the
    answer as decoded columns; (Query with no report, None) if the
    query raised."""
    from bench.record import Query
    import jax
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(f"query.{template}"):
            res, stats = srv.query(plan)
    except Exception as e:  # noqa: BLE001 — a failed query is counted
        dt = time.perf_counter() - t0
        log(f"{template} {params} failed after {dt:.3f} s: "
            f"{type(e).__name__}: {e}")
        return Query(template, params, dt, None), None
    dt = time.perf_counter() - t0
    rep = stats.report()
    q = Query(template, params, dt, rep, rows_probed(stats))
    return q, res.to_pydict(decode=True)


def warm_up(srv, cycle) -> None:
    for t, p, plan in cycle:
        q, _ = run_query(srv, t, p, plan)
        if q.report is None:
            raise BenchError(f"warm-up query {t} {p} failed")
        log(f"warm-up {t} {p}: {q.latency_s:.3f} s")


def check_answers(catalog, config, answers, queries) -> dict:
    """Compare every answer of the window with the plain reference's:
    {check: {"value", "limit"}}."""
    from bench.correct import compare, plain_tables
    from bench.reference import Reference
    ref = Reference(plain_tables(catalog))
    want: Dict[str, dict] = {}
    rows, gap, compared = 0, 0.0, 0
    t0 = time.perf_counter()
    for q, got in zip(queries, answers):
        if got is None:
            continue
        key = q.template + json.dumps(q.params, sort_keys=True)
        if key not in want:
            want[key] = ref.answer(q.template, q.params)
        r, g = compare(got, want[key])
        if r or g > config["limits"]["value_rel_gap"]:
            log(f"{q.template} {q.params}: {r} rows differ, "
                f"value gap {g!r}")
        rows, gap, compared = rows + r, max(gap, g), compared + 1
    log(f"reference: {compared} answers, {len(want)} distinct, "
        f"{time.perf_counter() - t0:.3f} s")
    lim = config["limits"]
    return {"answers_missing": {"value": len(answers) - compared,
                                "limit": 0},
            "rows_differing": {"value": rows, "limit": lim["rows_differing"]},
            "value_rel_gap": {"value": gap, "limit": lim["value_rel_gap"]}}


def checks_pass(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def read_metrics(spec: dict, workload: str, run, trace: bool) -> dict:
    out = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        path = os.path.join(BENCH, "metrics", m["name"] + ".py")
        value = load_module(path).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def chip_peaks(kind: str) -> dict:
    """The `bench/peaks.json` entry of a device kind; a kind the table
    does not hold is an error, never a default."""
    peaks = load_json("bench", "peaks.json")["devices"]
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def resolve(workload: str):
    """(BENCHMARK.json, its cell, the cell's configuration and traffic)."""
    spec = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = load_json("bench", "configs", cell["config"] + ".json")
    traffic = load_json("bench", "traffic", cell["traffic"] + ".json")
    return spec, cell, config, traffic


def measure(args, spec, cell, config, traffic, on_chip=True) -> dict:
    """The whole run; returns the result object. `on_chip=False` skips
    the look for a chip and the device-mode checks (tests on the CPU)."""
    seed = args.seed % (1 << 63)
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    kind = devices[0].device_kind
    peaks = {}
    if on_chip:
        if platform != "tpu":
            raise BenchError(f"no TPU: JAX platform is {platform!r}")
        if len(devices) < cell["chips"]:
            raise BenchError(f"{cell['chips']} chips asked, "
                             f"{len(devices)} found")
        peaks = chip_peaks(kind)
        # every program, Mosaic's sub-second compiles too, goes to the
        # checkout's cache, so that only a checkout's first run compiles
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    tally = CompileTally()
    jax.monitoring.register_event_duration_secs_listener(tally)

    from repro.serve import QueryServer, ServeConfig
    from repro.tpch import generate
    from bench.record import Run
    t0 = time.perf_counter()
    catalog = generate(sf=config["sf"], seed=config["data_seed"])
    log(f"generate sf {config['sf']} seed {config['data_seed']}: "
        f"{time.perf_counter() - t0:.3f} s, "
        f"{len(catalog['lineitem'])} lineitem rows")
    stream = Stream(traffic, seed)
    serve = ServeConfig(**config["serve"])
    t0 = time.perf_counter()
    if traffic["warmup"] == "rehearse":
        # every query the window may send, once, on a server of its own:
        # every program is compiled, while the measured server's caches
        # have seen none of the window's queries
        with QueryServer(catalog, serve) as rehearsal:
            for i in range(stream.cycles):
                warm_up(rehearsal, stream.cycle(i))
        del rehearsal
    srv = QueryServer(catalog, serve)
    try:
        if traffic["warmup"] == "prime":
            # the measured server itself: its caches hold the window's
            # queries when the window starts
            for i in range(traffic["warmup_cycles"]):
                warm_up(srv, stream.cycle(i))
        log(f"warm-up: {time.perf_counter() - t0:.3f} s, "
            f"{tally.count} compilations, {tally.seconds:.3f} s: "
            f"{tally.top()}")
        if on_chip:
            bad = engines_off_device()
            if bad:
                raise BenchError(f"engines off the device: {bad}")

        queries, answers = [], []
        if args.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        gc_fence()
        compiles0 = tally.count
        window_start = time.perf_counter()
        setup_s = window_start - T_START
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                cycle = 0
                while True:
                    for t, p, plan in stream.cycle(cycle):
                        q, a = run_query(srv, t, p, plan)
                        queries.append(q)
                        answers.append(a)
                    end = time.perf_counter()
                    cycle += 1
                    if end - window_start >= args.seconds \
                            or cycle == stream.cycles:
                        break
        finally:
            if args.trace:
                jax.profiler.stop_trace()
        window_s = end - window_start
        compiles = tally.count - compiles0
        log(f"window: {len(queries)} queries, {cycle} cycles in "
            f"{window_s:.3f} s, {compiles} compilations")
        for t in stream.templates:
            lat = [q.latency_s for q in queries if q.template == t]
            log(f"window {t}: " + " ".join(f"{x:.3f}" for x in lat))
        if on_chip:
            bad = engines_off_device()
            backend = config["serve"]["join_backend"]
            bad += [f"{q.template} transfer on {q.report['transfer']['backend']}"
                    for q in queries if q.report is not None
                    and q.report["transfer"] is not None
                    and q.report["transfer"]["backend"] != backend]
            if bad:
                raise BenchError(f"off the device: {bad}")
        memory = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
        peak = max(int(m.get("peak_bytes_in_use", 0)) for m in memory)
    finally:
        srv.close()
    del srv
    gc.collect()

    run = Run(cell["name"], setup_s, window_s, queries, compiles, peaks)
    device = {"platform": platform, "kind": kind,
              "count": cell["chips"], "memory_peak_bytes": peak}
    result = {}
    if args.trace:
        from bench import trace as trace_mod
        t0 = time.perf_counter()
        run.trace = trace_mod.Trace.load(TRACE_DIR, run, cell["chips"])
        log(f"trace read: {time.perf_counter() - t0:.3f} s")
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    metrics = read_metrics(spec, cell["name"], run, bool(args.trace))

    checks = check_answers(catalog, config, answers, queries)
    failed = sum(q.report is None for q in queries)
    out = {"correct": checks_pass(checks), "attempted": len(queries),
           "failed": failed, "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        steady_allocator()
        out = measure(args, *resolve(args.workload))
    except BenchError as e:
        log(f"FAIL: {e}")
        return 1
    for name, c in out["checks"].items():
        log(f"{name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # the repository root, not bench/, so that bench's modules are found
    # as `bench.*` and shadow nothing (bench/trace.py against the stdlib)
    sys.path[0] = ROOT
    sys.exit(main())
