"""Performance comparisons.

Five modes:

1. Backend comparison (PhysicalSpec layer): run the LDBC query set through
   every registered execution backend, check row-for-row result parity, and
   emit per-query timings to ``BENCH_backends.json``:

       PYTHONPATH=src python -m benchmarks.perf_compare --backends \
           [--sf 0.2] [--queries ic,cbo] [--repeats 3] [--out ...]

2. Prepared-query comparison (GraphIrBuilder / prepared lifecycle,
   DESIGN.md §3): for each parameterized query, time per-execution latency
   of the unprepared path (full parse + type-inference + RBO + CBO on every
   run) against ``GOpt.prepare(...).execute(bindings)`` across several
   bindings, on every backend, checking row parity between the two paths;
   emits ``BENCH_prepared.json``:

       PYTHONPATH=src python -m benchmarks.perf_compare --prepared \
           [--sf 0.2] [--repeats 3] [--out BENCH_prepared.json]

3. Residency comparison (OperatorSet v2, DESIGN.md §7): run the query set
   on the jax backend twice — the device-resident v2 path vs the v1-style
   host-staging path (PR-3 data plane: host binding tables, padded-block
   device round trips per op) — recording wall time and per-phase transfer
   counts for both; emits ``BENCH_residency.json`` and exits nonzero on a
   result mismatch or on any mid-plan device->host transfer in the v2 path
   (the residency invariants).  ``--gate-perf`` additionally fails queries
   where the resident path is slower beyond the noise tolerance — that
   gate is meaningful on a real accelerator; on interpret-mode CPU the
   "device" is host RAM, so point queries are eager-dispatch-bound and the
   round-trip path wins them (the JSON records the truth either way):

       PYTHONPATH=src python -m benchmarks.perf_compare --residency \
           [--sf 0.2] [--queries ic,rbo,typeinf] [--repeats 3] \
           [--gate-perf] [--out ...]

4. Fusion comparison (DESIGN.md §8): run the query set on the jax backend
   three ways — fused single-dispatch chain programs, the per-hop v2 loop
   (``chain_dispatch=False``), and the host-staged baseline — recording
   walls plus per-query fused dispatch/compile counts; emits
   ``BENCH_fusion.json`` and exits nonzero on a result mismatch or when the
   fused path's geomean wall regresses against the per-hop loop on the
   ic/point-query set:

       PYTHONPATH=src python -m benchmarks.perf_compare --fusion \
           [--sf 0.2] [--queries ic,cbo,rbo,typeinf] [--repeats 3] [--out ...]

5. Serving comparison (QueryServer continuous batching, DESIGN.md §9): an
   open-loop seeded-Poisson request stream over an Appendix-A query mix is
   served two ways per backend — through the continuous-batching
   ``QueryServer`` (per-plan waves via ``execute_many``) and sequentially
   (one ``execute`` per request at its scheduled arrival) — recording
   p50/p99 latency against the *scheduled* arrivals, throughput, wave
   shapes, and per-wave compile counts; emits ``BENCH_serve.json`` and
   exits nonzero on a result mismatch, on a batched-throughput geomean
   <= 1.0x sequential, or when a warmed server's waves still compile
   fused-chain programs:

       PYTHONPATH=src python -m benchmarks.perf_compare --serve \
           [--sf 0.1] [--requests 240] [--rate 2000] [--max-wave 16] \
           [--backend-list numpy,jax] [--out BENCH_serve.json]

6. Legacy sweep comparison (§Perf closing table) of two dry-run result files:

       PYTHONPATH=src python -m benchmarks.perf_compare \
           dryrun_results.json dryrun_results_optimized.json
"""
from __future__ import annotations

import argparse
import json
import sys
import time

ROW_CAP = 8_000_000


# ------------------------------------------------------------ backend mode

def _tables_equal(a, b) -> bool:
    """Row-for-row equality of two engine Tables."""
    import numpy as np
    if a.nrows != b.nrows or set(a.cols) != set(b.cols):
        return False
    return all(np.array_equal(a.cols[k], b.cols[k]) for k in a.cols)


def run_backends(args) -> dict:
    import numpy as np

    from benchmarks import queries as Q
    from repro.core.gopt import GOpt
    from repro.graphdb.ldbc import generate_ldbc

    from repro.core.physical_spec import get_spec
    backends = args.backend_list.split(",")
    for b in backends:        # fail fast, before the store build
        get_spec(b)
    sets = {"ic": (Q.QIC, Q.QIC_PARAMS),
            "cbo": (Q.QC, {}),
            "rbo": (Q.QR, Q.QR_PARAMS),
            "typeinf": (Q.QT, {})}
    t0 = time.time()
    print(f"# building LDBC-like store sf={args.sf} + GLogue ...", flush=True)
    gopt = GOpt(generate_ldbc(sf=args.sf, seed=7))
    print(f"# store: V={gopt.store.n_vertices} E={gopt.store.n_edges} "
          f"({time.time() - t0:.1f}s); backends: {backends}", flush=True)

    results = []
    for setname in args.queries.split(","):
        queries, params = sets[setname]
        for name, text in queries.items():
            opt = gopt.optimize(text, params.get(name))
            rec: dict = {"set": setname, "query": name, "match": True}
            ref = None
            for backend in backends:
                try:
                    # warmup run absorbs jit/Pallas compilation, then time
                    tbl, _ = gopt.execute(opt, backend=backend,
                                          max_rows=ROW_CAP)
                    best = float("inf")
                    for _ in range(args.repeats):
                        t1 = time.perf_counter()
                        tbl, _ = gopt.execute(opt, backend=backend,
                                              max_rows=ROW_CAP)
                        best = min(best, time.perf_counter() - t1)
                except (RuntimeError, MemoryError) as exc:
                    rec[f"{backend}_s"] = None
                    rec[f"{backend}_error"] = str(exc)[:120]
                    continue
                rec[f"{backend}_s"] = best
                if ref is None:
                    ref = tbl
                    rec["rows"] = tbl.nrows
                elif not _tables_equal(ref, tbl):
                    rec["match"] = False
            results.append(rec)
            times = " ".join(
                f"{b}={rec[f'{b}_s']:.4f}s" if rec.get(f"{b}_s") is not None
                else f"{b}=OT" for b in backends)
            print(f"{setname}/{name}: {times} rows={rec.get('rows')} "
                  f"match={rec['match']}", flush=True)

    mismatches = [r["query"] for r in results if not r["match"]]
    # a backend erroring while another succeeds leaves parity unverified
    # for that query — count it as a failure, not a silent skip
    unverified = [r["query"] for r in results
                  if r["match"]
                  and any(r.get(f"{b}_s") is None for b in backends)
                  and not all(r.get(f"{b}_s") is None for b in backends)]
    geo = {}
    base = backends[0]
    for b in backends[1:]:
        ratios = [r[f"{base}_s"] / r[f"{b}_s"] for r in results
                  if r.get(f"{base}_s") and r.get(f"{b}_s")]
        geo[f"{base}_over_{b}_geomean"] = (
            float(np.exp(np.mean(np.log(ratios)))) if ratios else None)
    out = {"sf": args.sf, "backends": backends, "repeats": args.repeats,
           "results": results, "mismatches": mismatches,
           "unverified": unverified, "summary": geo}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=float)
    print(f"# wrote {args.out}; mismatches={mismatches or 'none'} "
          f"unverified={unverified or 'none'} "
          f"summary={geo} ({time.time() - t0:.1f}s total)")
    return out


# ----------------------------------------------------------- prepared mode

# 3 parameter bindings per query (the serving scenario: one prepared plan,
# many executions with fresh values)
_PREPARED_BINDINGS = {
    "ic": [{"pid": 3}, {"pid": 5}, {"pid": 9}],
    "rbo5": [{"id1": 3, "id2": 7}, {"id1": 1, "id2": 4}, {"id1": 2, "id2": 9}],
    "rbo6": [{"id1": 3, "id2": 7, "len": 64}, {"id1": 1, "id2": 4, "len": 32},
             {"id1": 2, "id2": 9, "len": 128}],
}


def run_prepared(args) -> dict:
    import numpy as np

    from benchmarks import queries as Q
    from repro.core.gopt import GOpt
    from repro.core.physical_spec import get_spec
    from repro.graphdb.ldbc import generate_ldbc

    backends = args.backend_list.split(",")
    for b in backends:
        get_spec(b)
    cases = [(name, text, _PREPARED_BINDINGS["ic"])
             for name, text in Q.QIC.items()]
    cases.append(("Qr5", Q.QR["Qr5"], _PREPARED_BINDINGS["rbo5"]))
    cases.append(("Qr6", Q.QR["Qr6"], _PREPARED_BINDINGS["rbo6"]))

    t0 = time.time()
    print(f"# building LDBC-like store sf={args.sf} + GLogue ...", flush=True)
    gopt = GOpt(generate_ldbc(sf=args.sf, seed=7))
    print(f"# store: V={gopt.store.n_vertices} E={gopt.store.n_edges} "
          f"({time.time() - t0:.1f}s); backends: {backends}", flush=True)

    results, mismatches, regressions = [], [], []
    for backend in backends:
        for name, text, bindings in cases:
            rec = {"query": name, "backend": backend, "match": True,
                   "executions": len(bindings) * args.repeats}
            # warmup both paths (absorbs jit/Pallas compilation on jax)
            opt = gopt.optimize(text, bindings[0], backend=backend)
            gopt.execute(opt, backend=backend, max_rows=ROW_CAP,
                         params=bindings[0])
            pq = gopt.prepare(text, bindings[0], backend=backend)
            pq.execute(bindings[0], max_rows=ROW_CAP)

            counters0 = dict(gopt.compile_counters)
            un_s = pr_s = 0.0
            for params in bindings:
                for _ in range(args.repeats):
                    t1 = time.perf_counter()
                    opt = gopt.optimize(text, params, backend=backend)
                    ref, _ = gopt.execute(opt, backend=backend,
                                          max_rows=ROW_CAP, params=params)
                    un_s += time.perf_counter() - t1
                    t1 = time.perf_counter()
                    tbl, _ = pq.execute(params, max_rows=ROW_CAP)
                    pr_s += time.perf_counter() - t1
                    if not _tables_equal(ref, tbl):
                        rec["match"] = False
            if dict(gopt.compile_counters) != {
                    k: v + rec["executions"] for k, v in counters0.items()}:
                # unprepared path compiles once per execution; the prepared
                # path must add nothing on top of that
                rec["recompiled"] = True
                rec["match"] = False
            n = rec["executions"]
            rec["unprepared_s"] = un_s / n
            rec["prepared_s"] = pr_s / n
            rec["speedup"] = un_s / pr_s if pr_s else None
            results.append(rec)
            if not rec["match"]:
                mismatches.append(f"{backend}/{name}")
            if rec["prepared_s"] >= rec["unprepared_s"]:
                regressions.append(f"{backend}/{name}")
            print(f"{backend}/{name}: unprepared={rec['unprepared_s']:.5f}s "
                  f"prepared={rec['prepared_s']:.5f}s "
                  f"speedup={rec['speedup']:.1f}x match={rec['match']}",
                  flush=True)

    verify_overhead = _measure_verify_overhead(gopt.store, cases)
    print(f"# verify overhead: off={verify_overhead['off_s']:.4f}s "
          f"cached={verify_overhead['cached_s']:.4f}s "
          f"ratio={verify_overhead['overhead']:.2%} "
          f"(gate <{VERIFY_OVERHEAD_TOL:.0%})", flush=True)

    geo = {}
    for backend in backends:
        sp = [r["speedup"] for r in results
              if r["backend"] == backend and r["speedup"]]
        geo[f"{backend}_speedup_geomean"] = (
            float(np.exp(np.mean(np.log(sp)))) if sp else None)
    # gate on the aggregate, not per-query regressions: single-query timing
    # flips are noise at smoke scale, but a backend whose *geomean* prepared
    # speedup drops to <=1x has lost the point of preparing
    slow_backends = [b for b in backends
                     if geo.get(f"{b}_speedup_geomean") is not None
                     and geo[f"{b}_speedup_geomean"] <= 1.0]
    out = {"sf": args.sf, "backends": backends, "repeats": args.repeats,
           "results": results, "mismatches": mismatches,
           "regressions": regressions, "slow_backends": slow_backends,
           "verify_overhead": verify_overhead, "summary": geo}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=float)
    print(f"# wrote {args.out}; mismatches={mismatches or 'none'} "
          f"regressions={regressions or 'none'} "
          f"slow_backends={slow_backends or 'none'} "
          f"verify_overhead={verify_overhead['overhead']:.2%} "
          f"summary={geo} ({time.time() - t0:.1f}s total)")
    return out


# verify="cached" must stay under 5% of total prepare time (DESIGN.md §12);
# the absolute slack keeps sub-millisecond totals from tripping the ratio
VERIFY_OVERHEAD_TOL = 0.05
VERIFY_OVERHEAD_SLACK_S = 0.025


def _measure_verify_overhead(store, cases, rounds: int = 3) -> dict:
    """Total prepare wall for the bench's case set with verification off vs
    ``verify="cached"`` — identical optimizer config in both arms.  The plan
    caches are cleared between rounds so every round pays the full pipeline,
    while the cached arm's verification memo persists (its steady state:
    one real verification per canonical plan form, memo hits after)."""
    from repro.core.gopt import GOpt

    totals = {}
    for mode in ("off", "cached"):
        gopt = GOpt(store, build_glogue=False)
        t = 0.0
        for _ in range(rounds):
            gopt._plan_cache.clear()
            gopt._text_cache.clear()
            t1 = time.perf_counter()
            for _name, text, bindings in cases:
                gopt.prepare(text, bindings[0], verify=mode)
            t += time.perf_counter() - t1
        totals[mode] = t
    overhead = ((totals["cached"] - totals["off"]) / totals["off"]
                if totals["off"] else 0.0)
    return {"off_s": totals["off"], "cached_s": totals["cached"],
            "overhead": overhead,
            "exceeded": (overhead >= VERIFY_OVERHEAD_TOL
                         and totals["cached"] - totals["off"]
                         > VERIFY_OVERHEAD_SLACK_S)}


# ---------------------------------------------------------- residency mode

# best-of-repeats still jitters a few percent at smoke scale; the gate
# flags a query only when the resident path loses beyond this factor
RESIDENCY_TOL = 1.10


def _mid_plan_d2h(transfers: dict | None) -> int:
    from repro.core.physical_spec import TransferStats
    return TransferStats.mid_plan_d2h(transfers)


def run_residency(args) -> dict:
    """Device-resident (v2) vs host-staged (v1-style) execution on the jax
    backend: same optimized plans, same store, two data planes."""
    import numpy as np

    from benchmarks import queries as Q
    from repro.core.gopt import GOpt
    from repro.core.physical_spec import get_spec
    from repro.graphdb.engine import Engine
    from repro.graphdb.host_staging import HostStagingOperators
    from repro.graphdb.ldbc import generate_ldbc

    sets = {"ic": (Q.QIC, Q.QIC_PARAMS),
            "cbo": (Q.QC, {}),
            "rbo": (Q.QR, Q.QR_PARAMS),
            "typeinf": (Q.QT, {})}
    t0 = time.time()
    print(f"# building LDBC-like store sf={args.sf} + GLogue ...", flush=True)
    gopt = GOpt(generate_ldbc(sf=args.sf, seed=7))
    print(f"# store: V={gopt.store.n_vertices} E={gopt.store.n_edges} "
          f"({time.time() - t0:.1f}s)", flush=True)
    resident = get_spec("jax").operators(gopt.store)
    staged = HostStagingOperators(resident)
    ts = resident.transfer_stats

    def timed(run, *a, **kw):
        run(*a, **kw)                     # warmup: jit/Pallas compilation
        best, tbl, stats = float("inf"), None, None
        for _ in range(args.repeats):
            t1 = time.perf_counter()
            tbl, stats = run(*a, **kw)
            best = min(best, time.perf_counter() - t1)
        return best, tbl, stats

    results, mismatches, leaks, regressions = [], [], [], []
    for setname in args.queries.split(","):
        queries, params = sets[setname]
        for name, text in queries.items():
            opt = gopt.optimize(text, params.get(name), backend="jax")
            try:
                ts.reset()
                v2_s, v2_tbl, v2_stats = timed(
                    gopt.execute, opt, backend="jax", max_rows=ROW_CAP)
                ts.reset()
                v1_s, v1_tbl, v1_stats = timed(
                    Engine(gopt.store, backend=staged,
                           max_rows=ROW_CAP).run, opt.logical, opt.physical)
            except (RuntimeError, MemoryError) as exc:
                results.append({"set": setname, "query": name,
                                "error": str(exc)[:120]})
                print(f"{setname}/{name}: ERROR {str(exc)[:80]}", flush=True)
                continue
            rec = {
                "set": setname, "query": name, "rows": v2_tbl.nrows,
                "match": _tables_equal(v1_tbl, v2_tbl),
                "v1_host_staged_s": v1_s, "v2_resident_s": v2_s,
                "speedup": v1_s / v2_s if v2_s else None,
                "v2_mid_plan_d2h": _mid_plan_d2h(v2_stats.transfers),
                "v1_mid_plan_d2h": _mid_plan_d2h(v1_stats.transfers),
                "v2_transfers": v2_stats.transfers,
            }
            results.append(rec)
            if not rec["match"]:
                mismatches.append(name)
            if rec["v2_mid_plan_d2h"]:
                leaks.append(name)
            if v2_s > v1_s * RESIDENCY_TOL:
                regressions.append(name)
            print(f"{setname}/{name}: v1={v1_s:.4f}s v2={v2_s:.4f}s "
                  f"speedup={rec['speedup']:.2f}x d2h(v1/v2)="
                  f"{rec['v1_mid_plan_d2h']}/{rec['v2_mid_plan_d2h']} "
                  f"rows={rec['rows']} match={rec['match']}", flush=True)

    ok = [r for r in results if "error" not in r and r["speedup"]]
    geo = (float(np.exp(np.mean(np.log([r["speedup"] for r in ok]))))
           if ok else None)
    out = {"sf": args.sf, "repeats": args.repeats, "tolerance": RESIDENCY_TOL,
           "results": results, "mismatches": mismatches,
           "mid_plan_d2h_leaks": leaks, "regressions": regressions,
           "summary": {"resident_over_staged_geomean": geo},
           "note": "interpret-mode CPU: the 'device' is host RAM, so "
                   "dispatch-bound point queries favor the host-staged "
                   "path; the resident path pays off where padded-block "
                   "transfer volume dominates, and the speedup column is "
                   "expected to flip broadly on a real accelerator "
                   "(ROADMAP: re-measure on TPU)"}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=float)
    print(f"# wrote {args.out}; mismatches={mismatches or 'none'} "
          f"leaks={leaks or 'none'} regressions={regressions or 'none'} "
          f"geomean={geo} ({time.time() - t0:.1f}s total)")
    return out


# ------------------------------------------------------------- fusion mode

def run_fusion(args) -> dict:
    """Fused single-dispatch chain execution vs the per-hop v2 loop vs the
    host-staged baseline on the jax backend (DESIGN.md §8): same optimized
    plans, three execution paths, with per-query dispatch/compile counts
    from the KernelStats ledger.  Gates on result parity and on the fused
    path's geomean wall being no worse than the per-hop v2 path over the
    ic/point-query set (the dispatch-bound workloads PR 4 measured)."""
    import numpy as np

    from benchmarks import queries as Q
    from repro.core.gopt import GOpt
    from repro.core.physical_spec import get_spec
    from repro.graphdb.engine import Engine
    from repro.graphdb.host_staging import HostStagingOperators
    from repro.graphdb.ldbc import generate_ldbc

    sets = {"ic": (Q.QIC, Q.QIC_PARAMS),
            "cbo": (Q.QC, {}),
            "rbo": (Q.QR, Q.QR_PARAMS),
            "typeinf": (Q.QT, {})}
    t0 = time.time()
    print(f"# building LDBC-like store sf={args.sf} + GLogue ...", flush=True)
    gopt = GOpt(generate_ldbc(sf=args.sf, seed=7))
    print(f"# store: V={gopt.store.n_vertices} E={gopt.store.n_edges} "
          f"({time.time() - t0:.1f}s)", flush=True)
    resident = get_spec("jax").operators(gopt.store)
    staged = HostStagingOperators(resident)

    def timed(run, *a, **kw):
        run(*a, **kw)                     # warmup (jit / chain measuring)
        run(*a, **kw)                     # warmup 2 (fused compile)
        best, stats = float("inf"), None
        tbl = None
        for _ in range(args.repeats):
            t1 = time.perf_counter()
            tbl, stats = run(*a, **kw)
            best = min(best, time.perf_counter() - t1)
        return best, tbl, stats

    results, mismatches, regressions = [], [], []
    for setname in args.queries.split(","):
        queries, params = sets[setname]
        for name, text in queries.items():
            opt = gopt.optimize(text, params.get(name), backend="jax")
            try:
                ref, _ = gopt.execute(opt, backend="numpy",
                                      max_rows=ROW_CAP)
                fused_s, f_tbl, f_stats = timed(
                    gopt.execute, opt, backend="jax", max_rows=ROW_CAP)
                hop_s, h_tbl, h_stats = timed(
                    gopt.execute, opt, backend="jax", max_rows=ROW_CAP,
                    chain_dispatch=False)
                v1_s, v1_tbl, _ = timed(
                    Engine(gopt.store, backend=staged,
                           max_rows=ROW_CAP).run, opt.logical, opt.physical)
            except (RuntimeError, MemoryError) as exc:
                results.append({"set": setname, "query": name,
                                "error": str(exc)[:120]})
                print(f"{setname}/{name}: ERROR {str(exc)[:80]}", flush=True)
                continue
            match = (_tables_equal(ref, f_tbl) and _tables_equal(ref, h_tbl)
                     and _tables_equal(ref, v1_tbl))
            kern = f_stats.kernels or {}
            rec = {
                "set": setname, "query": name, "rows": f_tbl.nrows,
                "match": match,
                "fused_s": fused_s, "perhop_v2_s": hop_s,
                "host_staged_s": v1_s,
                "fused_over_perhop": hop_s / fused_s if fused_s else None,
                "fused_dispatches": kern.get("dispatch:fused_chain", 0),
                "fused_compiles": kern.get("compile:fused_chain", 0),
                "fused_kernels": kern,
                "perhop_kernels": h_stats.kernels,
            }
            results.append(rec)
            if not match:
                mismatches.append(name)
            print(f"{setname}/{name}: fused={fused_s:.4f}s "
                  f"perhop={hop_s:.4f}s staged={v1_s:.4f}s "
                  f"speedup={rec['fused_over_perhop']:.2f}x "
                  f"chain_dispatches={rec['fused_dispatches']} "
                  f"match={match}", flush=True)

    ok = [r for r in results if "error" not in r and r["fused_over_perhop"]]
    geo = (float(np.exp(np.mean(np.log([r["fused_over_perhop"]
                                        for r in ok])))) if ok else None)
    # the ic/point set of the acceptance gate: the LDBC-interactive queries
    # plus the rbo point lookups — not the whole rbo set, whose join-heavy
    # members would average a point-query regression away
    ic_ok = [r for r in ok
             if r["set"] == "ic" or r["query"] in ("Qr5", "Qr6")]
    ic_geo = (float(np.exp(np.mean(np.log([r["fused_over_perhop"]
                                           for r in ic_ok]))))
              if ic_ok else None)
    # acceptance gate: fused geomean wall <= per-hop v2 on the ic/point set
    if ic_geo is not None and ic_geo < 1.0:
        regressions.append(f"ic/point geomean {ic_geo:.3f}x < 1.0")
    out = {"sf": args.sf, "repeats": args.repeats, "results": results,
           "mismatches": mismatches, "regressions": regressions,
           "summary": {"fused_over_perhop_geomean": geo,
                       "ic_point_fused_over_perhop_geomean": ic_geo},
           "note": "fused = single-dispatch chain programs (DESIGN.md §8); "
                   "perhop_v2 = chain_dispatch=False device-resident loop; "
                   "host_staged = PR-3-style padded-block round trips. "
                   "Timings are CPU/interpret; chain compile counts "
                   "amortize across the repeats (pow2-bucketed cache)."}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=float)
    print(f"# wrote {args.out}; mismatches={mismatches or 'none'} "
          f"regressions={regressions or 'none'} "
          f"geomean={geo} ic_point={ic_geo} ({time.time() - t0:.1f}s total)")
    return out


# ------------------------------------------------------------- legacy mode

def legacy_sweep(base_p: str, opt_p: str) -> None:
    base = {(r["arch"], r["shape"], r["mesh"]): r
            for r in json.load(open(base_p))}
    opt = {(r["arch"], r["shape"], r["mesh"]): r
           for r in json.load(open(opt_p))}
    print("| arch | shape | mem GB (base->opt) | T_m s | T_x s | note |")
    print("|---|---|---|---|---|---|")
    for key in base:
        if key[2] != "16x16":
            continue
        b, o = base.get(key), opt.get(key)
        if not (b and o and b["status"] == "OK" and o["status"] == "OK"):
            continue
        bm = b["bytes_per_device"]["total_gb"]
        om = o["bytes_per_device"]["total_gb"]
        brf, orf = b.get("roofline", {}), o.get("roofline", {})
        note = ""
        if abs(bm - om) / max(bm, 1e-9) > 0.03:
            note = f"{bm/max(om,1e-9):.1f}x mem"
        print(f"| {key[0]} | {key[1]} | {bm:.1f} -> {om:.1f} | "
              f"{brf.get('t_memory_s', 0):.3g} -> "
              f"{orf.get('t_memory_s', 0):.3g} | "
              f"{brf.get('t_collective_s', 0):.3g} -> "
              f"{orf.get('t_collective_s', 0):.3g} | {note} |")


# ------------------------------------------------------------- serve mode

def run_serve(args) -> dict:
    """Open-loop serving comparison (DESIGN.md §9): the same seeded-Poisson
    arrival schedule over an Appendix-A query mix, served through the
    continuous-batching QueryServer vs sequentially, per backend.  Latency
    is measured against the scheduled arrival time (open-loop: a slow
    server pays its own queueing), so the p99 comparison is honest about
    backlog.  Gates on row parity of every batched result against the
    per-binding reference, on batched throughput beating sequential
    (geomean across backends), and on a warmed server's waves recording
    zero fused-chain compiles."""
    import numpy as np

    from benchmarks import queries as Q
    from repro.core.gopt import GOpt
    from repro.graphdb.ldbc import generate_ldbc
    from repro.graphdb.serve import ServeStats, _percentile

    t0 = time.time()
    print(f"# building LDBC-like store sf={args.sf} + GLogue ...", flush=True)
    gopt = GOpt(generate_ldbc(sf=args.sf, seed=7))
    print(f"# store: V={gopt.store.n_vertices} E={gopt.store.n_edges} "
          f"({time.time() - t0:.1f}s)", flush=True)

    # Appendix-A serving mix: parameterized interactive/point lookups (the
    # natural batching workload) plus one parameter-free aggregate (perfect
    # plan coalescing).  Parameter values draw zipf-like from a small hot
    # set — serving traffic has hot keys, which is what within-wave
    # duplicate suppression and the union pattern pass both exploit.
    zw = 1.0 / np.arange(1, 41)
    zw /= zw.sum()

    def zipf_id(rng):
        return int(rng.choice(40, p=zw))

    def mix(rng):
        return [
            ("ic1", Q.QIC["ic1"], lambda: {"pid": zipf_id(rng)}),
            ("Qr5", Q.QR["Qr5"], lambda: {"id1": zipf_id(rng),
                                          "id2": zipf_id(rng)}),
            ("Qr6", Q.QR["Qr6"], lambda: {"id1": zipf_id(rng),
                                          "id2": zipf_id(rng),
                                          "len": 64}),
            ("Qt1", Q.QT["Qt1"], lambda: None),
        ][int(rng.integers(0, 4))]

    rng = np.random.default_rng(args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    schedule = []
    for at in arrivals:
        name, text, draw = mix(rng)
        schedule.append((float(at), (name, text, draw())))

    results, mismatches, regressions = [], [], []
    for backend in args.backend_list.split(","):
        pqs = {name: gopt.prepare(text, backend=backend)
               for _, (name, text, _p) in schedule}
        # per-binding references double as the warmup (jit, chains, tails)
        ref = {}
        for _, (name, _t, params) in schedule:
            k = (name, tuple(sorted((params or {}).items())))
            if k not in ref:
                ref[k] = pqs[name].execute(params, max_rows=ROW_CAP)[0]

        srv = gopt.serve(backend=backend, max_wave=args.max_wave,
                         max_pending=args.requests + 1, overlap=True)
        # warmup epochs: replay the full schedule through the server.  At
        # an offered rate above capacity the backlog makes wave formation
        # deterministic (FIFO pick + pow2 sizing over an already-full
        # queue), so the measured epoch re-forms the same waves and every
        # traced program — fused chains (capacity growth recompiles once),
        # bucketed tails, shape-dependent glue — is warm.
        wbase = time.perf_counter()
        for _ in range(2):
            for at, (name, text, params) in schedule:
                srv.submit(text, params, arrival_s=wbase + at)
            srv.drain()
        srv.stats = ServeStats()

        # measured epoch: the offered rate is far above service capacity,
        # so the server is backlog-bound from the first wave — pre-queuing
        # the arrival stream (with scheduled arrival stamps, which is what
        # latency is measured against) is the saturated open-loop regime,
        # and keeps wave formation identical to the warmup epochs
        base = time.perf_counter()
        reqs = []
        for at, (name, text, params) in schedule:
            reqs.append((name, srv.submit(text, params,
                                          arrival_s=base + at)))
        srv.drain()
        assert all(r.status == "done" for _, r in reqs)
        batch_span = max(r.finish_s for _, r in reqs) - base - schedule[0][0]
        batch_lat = [r.latency_s for _, r in reqs]
        for name, r in reqs:
            k = (name, tuple(sorted((r.params or {}).items())))
            if not _tables_equal(ref[k], r.table):
                mismatches.append(f"{backend}/{name}{r.params}")
        s = srv.stats.summary()
        warm_chain_compiles = srv.stats.kernels.get("compile:fused_chain",
                                                    0)

        # containment overhead (DESIGN.md §13): the same saturated epoch
        # on the default contained path vs ``containment=False`` (the
        # legacy direct dispatch) — the happy-path cost of the wave
        # try/except + breaker bookkeeping, gated under 5% (min-of-2
        # epochs each to shed scheduler noise)
        def epoch_span(server):
            ebase = time.perf_counter()
            ereqs = [server.submit(text, params, arrival_s=ebase + at)
                     for at, (_n, text, params) in schedule]
            server.drain()
            assert all(r.status == "done" for r in ereqs)
            return max(r.finish_s for r in ereqs) - ebase - schedule[0][0]

        cont_span = min(epoch_span(srv), epoch_span(srv))
        srv.close()
        srv0 = gopt.serve(backend=backend, max_wave=args.max_wave,
                          max_pending=args.requests + 1, overlap=True,
                          containment=False)
        epoch_span(srv0)                                         # warmup
        plain_span = min(epoch_span(srv0), epoch_span(srv0))
        srv0.close()
        containment_overhead = cont_span / plain_span - 1.0

        # sequential baseline: same schedule, one execute per request at
        # its scheduled arrival
        base = time.perf_counter()
        seq_lat, last = [], 0.0
        for at, (name, _t, params) in schedule:
            now = time.perf_counter() - base
            if now < at:
                time.sleep(at - now)
            pqs[name].execute(params, max_rows=ROW_CAP)
            last = time.perf_counter() - base
            seq_lat.append(last - at)
        seq_span = last - schedule[0][0]

        rec = {
            "backend": backend,
            "requests": len(schedule),
            "offered_rate_rps": args.rate,
            "batched_throughput_rps": len(schedule) / batch_span,
            "sequential_throughput_rps": len(schedule) / seq_span,
            "throughput_speedup": seq_span / batch_span,
            "batched_p50_ms": _percentile(batch_lat, 50) * 1e3,
            "batched_p99_ms": _percentile(batch_lat, 99) * 1e3,
            "sequential_p50_ms": _percentile(seq_lat, 50) * 1e3,
            "sequential_p99_ms": _percentile(seq_lat, 99) * 1e3,
            "waves": s["waves"],
            "mean_wave_size": s["mean_wave_size"],
            "mean_occupancy": s["mean_occupancy"],
            "queue_delay_p50_ms": s["queue_delay_p50_ms"],
            "exec_p50_ms": s["exec_p50_ms"],
            "dropped": s["dropped"],
            "deduped": s["deduped"],
            "fallbacks": s["fallbacks"],
            "warm_chain_compiles": warm_chain_compiles,
            "compiles": s["compiles"],
            "waves_with_compiles": s["waves_with_compiles"],
            "containment_overhead": containment_overhead,
        }
        results.append(rec)
        if warm_chain_compiles:
            regressions.append(f"{backend}: warmed server compiled "
                               f"{warm_chain_compiles} chain program(s)")
        if containment_overhead > 0.05:
            regressions.append(
                f"{backend}: containment overhead "
                f"{containment_overhead * 100:.1f}% > 5% on the happy path")
        print(f"{backend}: batched {rec['batched_throughput_rps']:.1f} rps "
              f"(p99 {rec['batched_p99_ms']:.0f}ms) vs sequential "
              f"{rec['sequential_throughput_rps']:.1f} rps "
              f"(p99 {rec['sequential_p99_ms']:.0f}ms) -> "
              f"{rec['throughput_speedup']:.2f}x, "
              f"{s['waves']} waves mean={s['mean_wave_size']:.1f}, "
              f"containment overhead {containment_overhead * 100:+.1f}%",
              flush=True)

    speedups = [r["throughput_speedup"] for r in results]
    geo = (float(np.exp(np.mean(np.log(speedups)))) if speedups else None)
    if geo is not None and geo <= 1.0:
        regressions.append(f"batched/sequential throughput geomean "
                           f"{geo:.3f}x <= 1.0")
    out = {"sf": args.sf, "requests": args.requests, "rate": args.rate,
           "max_wave": args.max_wave, "seed": args.seed,
           "results": results, "mismatches": mismatches,
           "regressions": regressions,
           "summary": {"batched_over_sequential_geomean": geo},
           "note": "open-loop seeded-Poisson arrivals; latency measured "
                   "against scheduled arrival times, so queueing under an "
                   "overloaded sequential baseline shows up in its p99. "
                   "Timings are CPU/interpret-mode."}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=float)
    print(f"# wrote {args.out}; mismatches={mismatches or 'none'} "
          f"regressions={regressions or 'none'} geomean={geo} "
          f"({time.time() - t0:.1f}s total)")
    return out


def run_sharded(args) -> dict:
    """Sharded-backend scaling sweep (DESIGN.md §10): run the query set on
    the mesh-partitioned backend at each ``--shards`` count on a
    host-count-faked device mesh, checking row parity against numpy,
    proving the exchange contract (collectives recorded, zero mid-plan
    device->host transfers) and recording shard-count scaling curves to
    ``BENCH_sharded.json``.  The store comes from the *streamed* generator
    so ``--sf`` can exceed single-device generation sizes."""
    import numpy as np

    from benchmarks import queries as Q
    from repro.core.gopt import GOpt
    from repro.core.physical_spec import TransferStats
    from repro.graphdb.ldbc import generate_ldbc_streamed

    sets = {"ic": (Q.QIC, Q.QIC_PARAMS),
            "cbo": (Q.QC, {}),
            "rbo": (Q.QR, Q.QR_PARAMS),
            "typeinf": (Q.QT, {})}
    shard_counts = [int(s) for s in args.shards.split(",")]
    t0 = time.time()
    print(f"# building streamed LDBC-like store sf={args.sf} ...",
          flush=True)
    store = generate_ldbc_streamed(sf=args.sf, seed=args.seed)
    gn = GOpt(store)                     # numpy parity reference
    import jax
    avail = len(jax.devices())
    print(f"# store: V={store.n_vertices} E={store.n_edges} "
          f"({time.time() - t0:.1f}s); mesh devices: {avail}; "
          f"shard sweep: {shard_counts}", flush=True)
    gs = {S: GOpt(store, backend="sharded", devices=S)
          for S in shard_counts}

    results = []
    mismatches, leaks, silent = [], [], []
    for setname in args.queries.split(","):
        queries, params = sets[setname]
        for name, text in queries.items():
            p = params.get(name)
            ref, _ = gn.run(text, params=p)
            rec: dict = {"set": setname, "query": name, "rows": ref.nrows,
                         "match": True, "shards": {}}
            for S in shard_counts:
                try:
                    tbl, st = gs[S].run(text, params=p)   # warmup/compile
                    best = float("inf")
                    for _ in range(args.repeats):
                        t1 = time.perf_counter()
                        tbl, st = gs[S].run(text, params=p)
                        best = min(best, time.perf_counter() - t1)
                except (RuntimeError, MemoryError) as exc:
                    rec["shards"][str(S)] = {"error": str(exc)[:120]}
                    silent.append(f"{name}@{S}")
                    continue
                ex = st.exchanges or {}
                srec = {
                    "wall_s": best,
                    "exchange_calls": sum(v["calls"] for v in ex.values()),
                    "exchange_elems": sum(v["elems"] for v in ex.values()),
                    "mid_plan_d2h": TransferStats.mid_plan_d2h(st.transfers),
                }
                rec["shards"][str(S)] = srec
                if not _tables_equal(ref, tbl):
                    rec["match"] = False
                if srec["mid_plan_d2h"]:
                    leaks.append(f"{name}@{S}")
                # the exchange proof: a multi-shard mesh must move frontier
                # data with recorded collectives, not silently on the host
                if S > 1 and ref.nrows and srec["exchange_calls"] == 0:
                    silent.append(f"{name}@{S}")
            if not rec["match"]:
                mismatches.append(name)
            results.append(rec)
            times = " ".join(
                f"S{S}={rec['shards'][str(S)]['wall_s']:.4f}s"
                if "wall_s" in rec["shards"].get(str(S), {}) else f"S{S}=ERR"
                for S in shard_counts)
            print(f"{setname}/{name}: {times} rows={rec['rows']} "
                  f"match={rec['match']}", flush=True)

    # shard-count scaling curve: geomean wall per shard count, relative to
    # the 1-shard mesh (collective overhead on a faked CPU mesh shows up
    # honestly as >1 walls; on a real interconnect this is the scaling
    # curve the cost model's alpha_exchange would be calibrated from)
    curve = {}
    base = str(shard_counts[0])
    for S in shard_counts:
        ratios = [r["shards"][base]["wall_s"] / r["shards"][str(S)]["wall_s"]
                  for r in results
                  if "wall_s" in r["shards"].get(base, {})
                  and "wall_s" in r["shards"].get(str(S), {})]
        curve[str(S)] = (float(np.exp(np.mean(np.log(ratios))))
                         if ratios else None)
    out = {"sf": args.sf, "shard_counts": shard_counts,
           "mesh_devices": avail, "repeats": args.repeats,
           "results": results, "mismatches": mismatches,
           "mid_plan_d2h_leaks": leaks, "silent_exchanges": silent,
           "speedup_vs_first_geomean": curve}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=float)
    print(f"# wrote {args.out}; mismatches={mismatches or 'none'} "
          f"leaks={leaks or 'none'} silent={silent or 'none'} "
          f"curve={curve} ({time.time() - t0:.1f}s total)")
    return out


def run_mutations(args) -> dict:
    """Mutation-under-serving sweep (DESIGN.md §11): read latency as a
    function of delta-overlay occupancy, per backend, plus the cost of
    compaction and the post-compaction recovery point.  Every rung gates
    on row parity against a frozen deep-copy oracle of the mutable store
    (MVCC snapshot semantics), and device backends gate on zero mid-plan
    device->host transfers with a non-empty overlay — the delta views
    must stay device-resident like the base CSR."""
    import copy

    import numpy as np

    from repro.core.gopt import GOpt
    from repro.core.physical_spec import TransferStats
    from repro.graphdb.delta import MutableGraphStore
    from repro.graphdb.ldbc import generate_ldbc

    t0 = time.time()
    print(f"# building LDBC-like store sf={args.sf} ...", flush=True)
    base = generate_ldbc(sf=args.sf, seed=7)
    print(f"# store: V={base.n_vertices} E={base.n_edges} "
          f"({time.time() - t0:.1f}s)", flush=True)
    queries = {
        "knows1": ("MATCH (a:PERSON)-[:KNOWS]->(b:PERSON) "
                   "RETURN a.id AS aid, b.id AS bid ORDER BY aid, bid"),
        "knows2": ("MATCH (a:PERSON)-[:KNOWS]->(b:PERSON)-[:KNOWS]->"
                   "(c:PERSON) RETURN a.id AS aid, count(c) AS n "
                   "ORDER BY aid"),
    }
    ladder = [0, 16, 64, 256, 1024]
    backends = args.backend_list.split(",")
    kt = next(t for t in base.out_csr if t.label == "KNOWS")
    off = base.v_offset["PERSON"]
    n_person = base.v_count["PERSON"]

    def rows(tbl):
        ks = sorted(tbl.cols)
        if tbl.nrows == 0:
            return []
        return sorted(zip(*[np.asarray(tbl.cols[k]).tolist() for k in ks]))

    results, mismatches, leaks = [], [], []
    for backend in backends:
        ms = MutableGraphStore(base)
        gopt = GOpt(ms, backend=backend)
        rng = np.random.default_rng(args.seed)
        rec = {"backend": backend, "rungs": [], "compaction": None}
        pre_rows = None
        for occ in ladder:
            while ms.overlay_edge_slots < occ:
                src = off + int(rng.integers(0, n_person))
                gid = ms.insert_vertex(
                    "PERSON", {"id": 700_000 + ms.overlay_edge_slots})
                ms.insert_edge(kt, src, gid)
            oracle = GOpt(copy.deepcopy(ms), backend="numpy")
            rung = {"overlay_edges": int(ms.overlay_edge_slots),
                    "queries": {}}
            for name, text in queries.items():
                gopt.run(text)                       # warm (compiles)
                walls = []
                for _ in range(max(args.repeats, 1)):
                    w0 = time.perf_counter()
                    tbl, stats = gopt.run(text)
                    walls.append(time.perf_counter() - w0)
                ref, _ = oracle.run(text)
                ok = rows(tbl) == rows(ref)
                if not ok:
                    mismatches.append(f"{backend}/{name}@{occ}")
                if backend != "numpy" and stats.transfers is not None:
                    d2h = TransferStats.mid_plan_d2h(stats.transfers)
                    if d2h:
                        leaks.append(f"{backend}/{name}@{occ}:{d2h}")
                rung["queries"][name] = {"wall_s": float(min(walls)),
                                         "rows": int(tbl.nrows),
                                         "match": ok}
            rec["rungs"].append(rung)
            print(f"#   {backend} occ={occ}: " +
                  " ".join(f"{n}={q['wall_s'] * 1e3:.1f}ms"
                           for n, q in rung["queries"].items()), flush=True)
        pre_rows = {n: rows(gopt.run(t)[0]) for n, t in queries.items()}
        w0 = time.perf_counter()
        ev = gopt.compact()
        compact_wall = time.perf_counter() - w0
        post = {}
        for name, text in queries.items():
            gopt.run(text)                           # recompile vs new base
            w0 = time.perf_counter()
            tbl, _ = gopt.run(text)
            post[name] = {"wall_s": float(time.perf_counter() - w0),
                          "match": rows(tbl) == pre_rows[name]}
            if not post[name]["match"]:
                mismatches.append(f"{backend}/{name}@post-compaction")
        rec["compaction"] = {"wall_s": float(compact_wall),
                             "merged_edges": ev["merged_edges"],
                             "ext_vertices": ev["ext_vertices"],
                             "post": post}
        print(f"#   {backend} compaction {compact_wall * 1e3:.0f}ms "
              f"(merged {ev['merged_edges']} edges); recovery " +
              " ".join(f"{n}={q['wall_s'] * 1e3:.1f}ms"
                       for n, q in post.items()), flush=True)
        results.append(rec)

    out = {"sf": args.sf, "ladder": ladder, "backends": backends,
           "repeats": args.repeats, "results": results,
           "mismatches": mismatches, "mid_plan_d2h_leaks": leaks}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=float)
    print(f"# wrote {args.out}; mismatches={mismatches or 'none'} "
          f"leaks={leaks or 'none'} ({time.time() - t0:.1f}s total)")
    return out


# ------------------------------------------------------------- CI registry

# the smoke-scale CI invocations: scripts/ci.sh drives these through
# --list-benches (name <TAB> argv) instead of hard-coding bench names
CI_BENCHES = [
    ("backends", "--backends --sf 0.05 --repeats 1 --queries ic "
                 "--out BENCH_backends_smoke.json"),
    ("prepared", "--prepared --sf 0.05 --repeats 1 "
                 "--out BENCH_prepared_smoke.json"),
    ("sharded", "--sharded --sf 0.05 --repeats 1 --queries ic "
                "--shards 1,4 --out BENCH_sharded_smoke.json"),
    ("mutations", "--mutations --sf 0.05 --repeats 1 "
                  "--out BENCH_mutations_smoke.json"),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backends", action="store_true",
                    help="compare PhysicalSpec execution backends")
    ap.add_argument("--prepared", action="store_true",
                    help="compare prepared vs unprepared execution")
    ap.add_argument("--residency", action="store_true",
                    help="compare device-resident vs host-staged jax paths")
    ap.add_argument("--fusion", action="store_true",
                    help="compare fused single-dispatch chains vs the "
                         "per-hop v2 loop vs the host-staged baseline")
    ap.add_argument("--serve", action="store_true",
                    help="compare continuous-batching QueryServer serving "
                         "vs sequential execution on an open-loop stream")
    ap.add_argument("--sharded", action="store_true",
                    help="sharded-backend shard-count scaling sweep on a "
                         "host-count-faked device mesh")
    ap.add_argument("--mutations", action="store_true",
                    help="read-latency vs delta-overlay occupancy sweep "
                         "with compaction cost and recovery")
    ap.add_argument("--shards", default="1,2,4,8",
                    help="--sharded: comma list of shard counts to sweep")
    ap.add_argument("--list-benches", action="store_true",
                    help="print the CI smoke-bench registry "
                         "(name<TAB>argv per line) and exit")
    ap.add_argument("--requests", type=int, default=200,
                    help="--serve: number of open-loop requests")
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="--serve: offered Poisson arrival rate (req/s); "
                         "above sequential capacity, so queues build and "
                         "coalescing has something to coalesce")
    ap.add_argument("--max-wave", type=int, default=16,
                    help="--serve: max requests coalesced per wave")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--gate-perf", action="store_true",
                    help="with --residency: also fail on per-query wall-time"
                         " regressions (meaningful on a real accelerator)")
    ap.add_argument("--backend-list", default="numpy,jax")
    ap.add_argument("--sf", type=float, default=0.2)
    ap.add_argument("--queries", default="ic,cbo",
                    help="comma list of ic,cbo,rbo,typeinf (--backends mode)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument("files", nargs="*",
                    help="legacy mode: base/optimized dryrun result files")
    args = ap.parse_args()
    if args.list_benches:
        for name, argv in CI_BENCHES:
            print(f"{name}\t{argv}")
        sys.exit(0)
    if args.sharded and "jax" not in sys.modules:
        # the faked CPU mesh must exist before the first jax import
        import os
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=8")
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.sharded:
        args.out = args.out or "BENCH_sharded.json"
        out = run_sharded(args)
        sys.exit(1 if out["mismatches"] or out["mid_plan_d2h_leaks"]
                 or out["silent_exchanges"] else 0)
    if args.mutations:
        args.out = args.out or "BENCH_mutations.json"
        out = run_mutations(args)
        sys.exit(1 if out["mismatches"] or out["mid_plan_d2h_leaks"] else 0)
    if args.backends:
        args.out = args.out or "BENCH_backends.json"
        out = run_backends(args)
        sys.exit(1 if out["mismatches"] or out["unverified"] else 0)
    if args.prepared:
        args.out = args.out or "BENCH_prepared.json"
        out = run_prepared(args)
        sys.exit(1 if out["mismatches"] or out["slow_backends"]
                 or out["verify_overhead"]["exceeded"] else 0)
    if args.residency:
        args.out = args.out or "BENCH_residency.json"
        out = run_residency(args)
        fail = bool(out["mismatches"] or out["mid_plan_d2h_leaks"])
        if args.gate_perf:
            fail = fail or bool(out["regressions"])
        sys.exit(1 if fail else 0)
    if args.fusion:
        args.out = args.out or "BENCH_fusion.json"
        out = run_fusion(args)
        sys.exit(1 if out["mismatches"] or out["regressions"] else 0)
    if args.serve:
        args.out = args.out or "BENCH_serve.json"
        out = run_serve(args)
        sys.exit(1 if out["mismatches"] or out["regressions"] else 0)
    base_p = args.files[0] if args.files else "dryrun_results.json"
    opt_p = (args.files[1] if len(args.files) > 1
             else "dryrun_results_optimized.json")
    legacy_sweep(base_p, opt_p)


if __name__ == "__main__":
    main()
