#!/usr/bin/env python3
"""Chip smoke: the TPC-H predicate-transfer -> join path on a TPU.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # the distributed join runtime only

One chip: generate the SF 1 TPC-H catalog from `--seed`; run the
compiled Pallas kernels once against their host references on its key
columns (Bloom build words bit for bit, single and fused probes, the
join map's build and lookup); start a `QueryServer` (strategy
pred-trans, Pallas Bloom engine on the device-resident data plane,
Pallas join backend, degradation ladder off, one worker), answer Q3,
Q5, Q9, Q17 and Q21 cold and then warm, and check every result
md5-bit-exact against the host oracle (the no-pred-trans numpy eager
`Executor` on the same catalog). Then the same queries on the jax
backends.

`--chips 4`: the same five queries through the distributed join runtime
(`engine="distributed"`, 4 device-backed shards over a real mesh),
checked against the same oracle, with the shard blocks and the
per-shard local joins required to land on 4 distinct devices.

The run fails — non-zero exit, no success line — when JAX finds no TPU,
when a kernel differs from its reference, when any engine runs in
Pallas interpret mode or routes builds or compaction to the host, when
a query degraded to a safer rung, when a cold query made no fused
device probe, or when any digest differs. Everything runs in this one process. Earlier lines are
informational (timings are host wall clock, compilation included); the
last line is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
QUERIES = (3, 5, 9, 17, 21)
SF = 1.0            # TPC-H scale factor: 6.0M lineitem rows


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileTally:
    """Backend compilations (compile-cache loads included) and their
    seconds, from JAX's compile-duration events."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count, self.seconds = 0, 0.0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += secs

    def __str__(self) -> str:
        return f"{self.count} compilations taking {self.seconds:.3f} s"


def oracle_digests(cat) -> dict:
    """md5 per query from the no-pred-trans numpy eager executor."""
    from repro.core.transfer import make_strategy
    from repro.relational import Executor
    from repro.relational.executor import ExecConfig
    from repro.relational.table import table_digest
    from repro.tpch import build_query
    cfg = ExecConfig(strategy=make_strategy("no-pred-trans"),
                     join_backend="numpy", late_materialize=False)
    out = {}
    for q in QUERIES:
        t0 = time.perf_counter()
        res, _ = Executor(cat, cfg).execute(build_query(q, sf=SF))
        out[q] = table_digest(res)
        log(f"oracle Q{q}: {time.perf_counter() - t0:.3f} s, "
            f"{len(res)} rows, md5 {out[q]}")
    return out


def engines_off_device() -> list:
    """Cached Bloom / join engines that run Pallas in interpret mode or
    route filter builds or survivor compaction through the host."""
    from repro.core import engine_bloom, engine_join
    engines = list(engine_bloom._ENGINES.values()) \
        + list(engine_join._ENGINES.values())
    return [f"{type(e).__name__}({e.backend}).{flag}" for e in engines
            for flag in ("interpret", "host_build", "host_compact")
            if getattr(e, flag, False)]


def _pad(a, n: int):
    """`a` zero-padded to n rows."""
    import numpy as np
    out = np.zeros(n, a.dtype)
    out[: len(a)] = a
    return out


def kernel_checks(cat, seed: int) -> None:
    """The compiled Pallas kernels against host references, on the SF 1
    catalog's order keys: Bloom build words must be equal bit for bit
    (no missing and no extra bits), probes equal to the numpy probe, and
    the join map must return each probe key's build row, -1 on a miss."""
    import jax
    import numpy as np
    from repro.core import bloom, hashing
    from repro.kernels.bloom import bloom as kb
    from repro.kernels.semijoin import ops as sj
    from repro.kernels.semijoin import semijoin as ks
    dev = jax.device_put
    rng = np.random.default_rng(seed)
    lkeys = cat["lineitem"].array("l_orderkey")
    okeys = cat["orders"].array("o_orderkey")
    nl = -(-len(lkeys) // kb.TILE) * kb.TILE
    no = -(-len(okeys) // kb.TILE) * kb.TILE
    l_lo, l_hi = hashing.key_halves(_pad(lkeys, nl))
    o_lo, o_hi = hashing.key_halves(_pad(okeys, no))

    t0 = time.perf_counter()
    words = []
    for keys, lo, hi, frac in ((lkeys, l_lo, l_hi, 0.5),
                               (okeys, o_lo, o_hi, 0.2)):
        m = _pad(rng.random(len(keys)) < frac, len(lo))
        nblocks = bloom.blocks_for(int(m.sum()))
        got = np.asarray(kb.build_pallas(dev(lo), dev(hi), dev(m),
                                         nblocks, interpret=False))
        want = bloom.build_np(lo, hi, m, nblocks)
        check(np.array_equal(got, want),
              f"bloom build_pallas ({nblocks} blocks): "
              f"{int(np.sum(got != want))} words differ from build_np")
        words.append(got)
    ref = [bloom.probe_np(w, o_lo, o_hi) for w in words]
    single = np.asarray(kb.probe_pallas(words[0], dev(o_lo), dev(o_hi),
                                        interpret=False))
    check(np.array_equal(single, ref[0]),
          "bloom probe_pallas differs from probe_np")
    both = ref[0] & ref[1]
    fused, counts = kb.multi_probe_pallas(
        tuple(words), (dev(o_lo),) * 2, (dev(o_hi),) * 2, len(okeys),
        interpret=False)
    want = both & (np.arange(len(both)) < len(okeys))
    check(np.array_equal(np.asarray(fused), want)
          and list(np.asarray(counts)) == [
              int(ref[0][:len(okeys)].sum()), int(want.sum())],
          "bloom multi_probe_pallas differs from the ANDed probe_np")
    log(f"kernels bloom: build {len(lkeys)} + {len(okeys)} keys, probe "
        f"{len(okeys)} keys, equal to numpy: "
        f"{time.perf_counter() - t0:.3f} s")

    t0 = time.perf_counter()
    cap = sj.capacity_for(len(okeys))
    jmask = _pad(rng.random(len(okeys)) < 0.9, no)
    klo, khi, occ, row = ks.build_rows_pallas(dev(o_lo), dev(o_hi),
                                              dev(jmask), cap,
                                              interpret=False)
    got = np.asarray(ks.lookup_pallas(klo, khi, occ, row, dev(l_lo),
                                      dev(l_hi), interpret=False)
                     )[: len(lkeys)]
    order = np.argsort(okeys, kind="stable")
    pos = np.minimum(np.searchsorted(okeys[order], lkeys), len(okeys) - 1)
    cand = order[pos]
    want = np.where((okeys[cand] == lkeys) & jmask[cand], cand, -1)
    check(int(np.asarray(occ).sum()) == int(jmask.sum()),
          "join map build_rows_pallas: occupancy != inserted keys")
    check(np.array_equal(got, want),
          f"join map lookup_pallas: {int(np.sum(got != want))} of "
          f"{len(lkeys)} rows differ from the numpy lookup")
    member = np.asarray(ks.probe_pallas(klo, khi, occ, dev(l_lo),
                                        dev(l_hi), interpret=False)
                        )[: len(lkeys)]
    check(np.array_equal(member, want >= 0),
          "join map probe_pallas differs from the numpy lookup")
    log(f"kernels join map: build {len(okeys)} keys (capacity {cap}), "
        f"look up {len(lkeys)} keys, equal to numpy: "
        f"{time.perf_counter() - t0:.3f} s")


def serve_backend(cat, oracle: dict, backend: str) -> None:
    """Cold then warm pass of every query through a `QueryServer`."""
    from repro.relational.table import table_digest
    from repro.serve import QueryServer, ServeConfig
    from repro.tpch import build_query
    cfg = ServeConfig(strategy="pred-trans", join_backend=backend,
                      degrade=False, workers=1)
    with QueryServer(cat, cfg) as srv:
        for q in QUERIES:
            plan = build_query(q, sf=SF)
            for phase in ("cold", "warm"):
                t0 = time.perf_counter()
                res, stats = srv.query(plan)
                dt = time.perf_counter() - t0
                rep = stats.report()
                dev = rep["device"]
                log(f"{backend} Q{q} {phase}: {dt:.3f} s, "
                    f"transfer backend {rep['transfer']['backend']}, "
                    f"h2d {dev['h2d_syncs']} x / {dev['h2d_bytes']} B, "
                    f"d2h {dev['d2h_syncs']} x / {dev['d2h_bytes']} B, "
                    f"round trips {dev['round_trips']}, "
                    f"fused {dev['fused_calls']}")
                check(not rep["degraded"],
                      f"{backend} Q{q} {phase} degraded: {rep['degraded']}")
                check(rep["transfer"]["backend"] == backend,
                      f"{backend} Q{q} transfer ran on "
                      f"{rep['transfer']['backend']}")
                if phase == "cold":
                    check(dev["fused_calls"] > 0,
                          f"{backend} Q{q}: no fused device probe")
                got = table_digest(res)
                check(got == oracle[q],
                      f"{backend} Q{q} {phase}: md5 {got} != oracle "
                      f"{oracle[q]}")
        bad = engines_off_device()
        check(not bad, f"engines off the device: {bad}")


def distributed(cat, oracle: dict, chips: int) -> None:
    """The five queries through the device-backed distributed runtime."""
    from repro.core.transfer import make_strategy
    from repro.relational import Executor
    from repro.relational.executor import ExecConfig
    from repro.relational.table import table_digest
    from repro.tpch import build_query
    cfg = ExecConfig(strategy=make_strategy("pred-trans", backend="pallas"),
                     join_backend="pallas", engine="distributed",
                     dist_shards=chips, dist_device=True)
    for q in QUERIES:
        t0 = time.perf_counter()
        ex = Executor(cat, cfg)
        res, stats = ex.execute(build_query(q, sf=SF))
        dt = time.perf_counter() - t0
        rep = stats.report()
        dist = stats.dist
        placed = sorted(ex.join_engine.exchange.placement)
        local = sorted(dist.local_devices)
        log(f"distributed Q{q}: {dt:.3f} s, shards {dist.nshards}, "
            f"strategies {rep['dist']['strategies']}, exchange devices "
            f"{placed}, local-join devices {local}")
        check(dist.device_backed and dist.nshards == chips,
              f"Q{q}: exchange not device-backed over {chips} shards")
        check(not rep["degraded"], f"Q{q} degraded: {rep['degraded']}")
        check(len(placed) == chips,
              f"Q{q}: shard blocks on devices {placed}")
        if rep["dist"]["strategies"].get("broadcast"):
            check(len(local) == chips,
                  f"Q{q}: per-shard local joins on devices {local}")
        got = table_digest(res)
        check(got == oracle[q],
              f"distributed Q{q}: md5 {got} != oracle {oracle[q]}")
    bad = engines_off_device()
    check(not bad, f"engines off the device: {bad}")


def run(args) -> dict:
    src = os.path.join(ROOT, "src")
    check(os.path.isdir(os.path.join(src, "repro")),
          f"the repro package is not at {src}")
    sys.path.insert(0, src)
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    check(platform == "tpu", f"no TPU: JAX platform is {platform!r}")
    want = args.chips or 1
    check(len(devices) >= want,
          f"{want} chips asked, {len(devices)} found")
    from repro.launch.cache import enable_compile_cache
    log(f"device_kind {devices[0].device_kind}, {len(devices)} device(s)")
    log(f"compile cache {enable_compile_cache(ROOT)}")
    tally = CompileTally()
    jax.monitoring.register_event_duration_secs_listener(tally)
    start = time.perf_counter()

    from repro.tpch import generate
    t0 = time.perf_counter()
    cat = generate(sf=SF, seed=args.seed)
    log(f"generate sf {SF} seed {args.seed}: "
        f"{time.perf_counter() - t0:.3f} s, "
        f"{len(cat['lineitem'])} lineitem rows")
    oracle = oracle_digests(cat)
    if args.chips:
        distributed(cat, oracle, args.chips)
    else:
        kernel_checks(cat, args.seed)
        for backend in ("pallas", "jax"):
            t0 = time.perf_counter()
            serve_backend(cat, oracle, backend)
            log(f"{backend}: {time.perf_counter() - t0:.3f} s for "
                f"{len(QUERIES)} queries cold + warm")
    log(f"in all {time.perf_counter() - start:.3f} s after start-up, "
        f"{tally}")
    return {"ok": True, "device": {"platform": platform,
                                   "kind": devices[0].device_kind,
                                   "count": len(devices)}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=0, choices=(0, 4),
                    help="4: run only the distributed path on 4 chips")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    try:
        result = run(args)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
