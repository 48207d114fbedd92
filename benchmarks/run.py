"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (plus a summary) and writes
EXPERIMENTS-ready JSON to benchmarks/results.json.

    PYTHONPATH=src python -m benchmarks.run            # default scale
    PYTHONPATH=src python -m benchmarks.run --sf 1.0 --tables cbo,ldbc
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks import paper_tables as T


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=1.0,
                    help="LDBC-like scale factor (paper uses 30..1000; "
                    "CPU-budget default 1.0)")
    ap.add_argument("--tables", default="typeinf,rbo,cbo,ldbc,scaling,"
                    "moneymule")
    ap.add_argument("--out", default="benchmarks/results.json")
    args = ap.parse_args()
    tables = set(args.tables.split(","))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    coll = T.Collector()
    results = {"sf": args.sf}
    t0 = time.time()
    print(f"# building LDBC-like store sf={args.sf} + GLogue ...", flush=True)
    gopt = T.make_gopt(args.sf)
    print(f"# store: V={gopt.store.n_vertices} E={gopt.store.n_edges} "
          f"glogue={len(gopt.glogue.freq)} entries "
          f"({time.time()-t0:.1f}s)", flush=True)

    if "typeinf" in tables:
        results["type_inference"] = T.table_type_inference(gopt, coll)
    if "rbo" in tables:
        results["rbo"] = T.table_rbo(gopt, coll)
    if "cbo" in tables:
        results["cbo"] = T.table_cbo(gopt, coll)
    if "ldbc" in tables:
        results["ldbc"] = T.table_ldbc(gopt, coll)
    if "scaling" in tables:
        results["scaling"] = T.table_scaling(coll)
    if "moneymule" in tables:
        results["money_mule"] = T.table_money_mule(gopt, coll)

    # ------------------------------------------------------------- summary
    def _geo(xs):
        xs = [x for x in xs if x == x and np.isfinite(x) and x > 0]
        return float(np.exp(np.mean(np.log(xs)))) if xs else float("nan")

    summary = {}
    if "type_inference" in results:
        summary["typeinf_geomean_speedup"] = _geo(
            [r["speedup"] for r in results["type_inference"]])
    if "rbo" in results:
        for rule in ("trim", "fuse", "filter"):
            summary[f"rbo_{rule}_geomean_speedup"] = _geo(
                [r["speedup"] for r in results["rbo"] if r["rule"] == rule])
    if "cbo" in results:
        summary["cbo_vs_neo4j_geomean"] = _geo(
            [r["neo4j_s"] / r["gopt_s"] for r in results["cbo"]
             if r["neo4j_s"] == r["neo4j_s"]])
        summary["cbo_vs_random_geomean"] = _geo(
            [r["rand_mean_s"] / r["gopt_s"] for r in results["cbo"]
             if r["rand_mean_s"] == r["rand_mean_s"]])
    if "ldbc" in results:
        summary["ldbc_vs_neo4j_geomean"] = _geo(
            [r["neo4j_s"] / r["gopt_s"] for r in results["ldbc"]
             if r["neo4j_s"] == r["neo4j_s"]])
        summary["ldbc_vs_random_geomean"] = _geo(
            [r.get("rand_mean_s", float("nan")) / r["gopt_s"]
             for r in results["ldbc"]
             if r.get("rand_mean_s", float("nan")) == r.get("rand_mean_s")])
    results["summary"] = summary
    for k, v in summary.items():
        coll.add(f"summary/{k}", float("nan"), f"{v:.2f}")

    with open(args.out, "w") as f:
        json.dump(results, f, indent=1, default=float)
    print(f"# wrote {args.out} ({time.time()-t0:.1f}s total)")


if __name__ == "__main__":
    main()
