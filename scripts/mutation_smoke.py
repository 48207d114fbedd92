#!/usr/bin/env python
"""Mutation smoke for CI (scripts/ci.sh): graph updates under serving
(DESIGN.md §11). A seeded interleaved read/write stream through the
QueryServer must hold MVCC-lite snapshot isolation — every read answers
as-of its admission snapshot, verified against frozen deep-copy oracles —
while the delta overlay stays device-resident (zero mid-plan
device->host transfers on the jax backend) and background compaction
preserves row parity, bumps the stats epoch, and re-pins warmed plans.

Usage: PYTHONPATH=src python scripts/mutation_smoke.py [--sf 0.05]
"""
import argparse
import copy
import sys

sys.path.insert(0, "src")
sys.path.insert(1, ".")

import numpy as np                                                 # noqa: E402

from repro.core.gopt import GOpt                                   # noqa: E402
from repro.core.physical_spec import TransferStats                 # noqa: E402
from repro.graphdb.delta import MutableGraphStore                  # noqa: E402
from repro.graphdb.ldbc import generate_ldbc                       # noqa: E402

N_ROUNDS = 24

Q_KNOWS = ("MATCH (a:PERSON)-[:KNOWS]->(b:PERSON) "
           "RETURN a.id AS aid, b.id AS bid ORDER BY aid, bid")
Q_2HOP = ("MATCH (a:PERSON)-[:KNOWS]->(b:PERSON)-[:KNOWS]->(c:PERSON) "
          "RETURN a.id AS aid, count(c) AS n ORDER BY aid")


def check(cond, msg):
    if not cond:
        print(f"MUTATION SMOKE FAIL: {msg}")
        sys.exit(1)


def rows(tbl):
    ks = sorted(tbl.cols)
    if tbl.nrows == 0:
        return []
    return sorted(zip(*[np.asarray(tbl.cols[k]).tolist() for k in ks]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.05)
    ap.add_argument("--backend", default="jax")
    args = ap.parse_args()
    base = generate_ldbc(sf=args.sf, seed=7)
    ms = MutableGraphStore(base)
    gopt = GOpt(ms, backend=args.backend)
    kt = next(t for t in base.out_csr if t.label == "KNOWS")
    off = base.v_offset["PERSON"]
    n_person = base.v_count["PERSON"]
    rng = np.random.default_rng(11)

    # ---- residency with a live overlay (before serving): zero mid-plan d2h
    for i in range(6):
        gid = ms.insert_vertex("PERSON", {"id": 500_000 + i})
        ms.insert_edge(kt, off + int(rng.integers(0, n_person)), gid)
    tbl, stats = gopt.run(Q_2HOP)
    check(tbl.nrows > 0, "overlay query returned no rows")
    if args.backend != "numpy":
        d2h = TransferStats.mid_plan_d2h(stats.transfers)
        check(d2h == 0, f"{d2h} mid-plan device->host transfer(s) "
              "with a non-empty overlay")

    # ---- interleaved read/write stream: snapshot isolation under serving
    srv = gopt.serve(max_wave=8, max_pending=4 * N_ROUNDS + 8)
    r = srv.submit(Q_KNOWS)
    srv.drain()
    base_rows = len(rows(r.table))
    oracle = []         # (request, frozen store at its admission)
    inserted = 0
    for i in range(N_ROUNDS):
        rq = srv.submit(Q_KNOWS)
        oracle.append((rq, copy.deepcopy(ms)))
        w = srv.submit_update("insert_vertex", "PERSON",
                              {"id": 600_000 + i})
        srv.drain()
        check(w.status == "done", f"write {i} failed: {w.status}")
        src = off + int(rng.integers(0, n_person))
        w2 = srv.submit_update("insert_edge", kt, src, w.result)
        if i % 5 == 4:
            srv.submit_update("delete_edge", kt, src, w.result)
        srv.drain()
        check(w2.status == "done" and w2.result, f"edge write {i} failed")
        inserted += 1 if i % 5 != 4 else 0
    for j, (rq, frozen) in enumerate(oracle):
        ref, _ = GOpt(frozen, backend="numpy").run(Q_KNOWS)
        check(rows(rq.table) == rows(ref),
              f"read {j} not isolated at its admission snapshot")
    r2 = srv.submit(Q_KNOWS)
    srv.drain()
    check(len(rows(r2.table)) == base_rows + inserted,
          f"post-stream read saw {len(rows(r2.table))} rows, "
          f"want {base_rows + inserted}")

    # ---- compaction through the server: parity + epoch bump + re-pin
    pre = rows(r2.table)
    epoch0 = gopt.plan_cache_info()["epoch"]
    ev = srv.compact()
    check(gopt.plan_cache_info()["epoch"] == epoch0 + 1,
          "compaction did not bump the stats epoch")
    check(ev["merged_edges"] > 0, f"nothing merged: {ev}")
    chain = srv.stats.kernels.get("compile:fused_chain", 0)
    r3 = srv.submit(Q_KNOWS)
    srv.drain()
    check(rows(r3.table) == pre, "row parity broken by compaction")
    post = srv.stats.kernels.get("compile:fused_chain", 0) - chain
    check(post == 0, f"re-pinned server compiled {post} chain program(s)")
    s = srv.stats.summary()
    srv.close()
    print(f"mutation smoke OK: {len(oracle)} isolated reads, "
          f"{s['writes']} writes, compaction merged {ev['merged_edges']} "
          f"edge(s) + {ev['ext_vertices']} vertex(es), "
          f"re-pinned {ev.get('repinned_plans', 0)} plan(s), "
          f"epoch {epoch0}->{epoch0 + 1}")


if __name__ == "__main__":
    main()
