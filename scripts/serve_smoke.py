#!/usr/bin/env python
"""Serving smoke for CI (scripts/ci.sh): a seeded 200-request stream through
the continuous-batching QueryServer (DESIGN.md §9) must complete with every
batched result row-identical to a sequential ``execute`` of the same
binding, a finite and bounded p99 latency, and — once the server is warm —
zero fused-chain compiles per wave.

Usage: PYTHONPATH=src python scripts/serve_smoke.py [--sf 0.05]
"""
import argparse
import sys

sys.path.insert(0, "src")
sys.path.insert(1, ".")

import math                                                        # noqa: E402

import numpy as np                                                 # noqa: E402

from benchmarks import queries as Q                                # noqa: E402
from repro.core.gopt import GOpt                                   # noqa: E402
from repro.graphdb.ldbc import generate_ldbc                       # noqa: E402
from repro.graphdb.serve import ServeStats                         # noqa: E402

N_REQUESTS = 200
MAX_WAVE = 16


def check(cond, msg):
    if not cond:
        print(f"SERVE SMOKE FAIL: {msg}")
        sys.exit(1)


def tables_equal(a, b) -> bool:
    if a.nrows != b.nrows or set(a.cols) != set(b.cols):
        return False
    return all(np.array_equal(np.asarray(a.cols[k]), np.asarray(b.cols[k]))
               for k in a.cols)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.05)
    ap.add_argument("--backend", default="jax")
    args = ap.parse_args()
    gopt = GOpt(generate_ldbc(sf=args.sf, seed=7))

    rng = np.random.default_rng(11)
    mix = [("ic1", Q.QIC["ic1"], lambda: {"pid": int(rng.integers(0, 20))}),
           ("Qr5", Q.QR["Qr5"], lambda: {"id1": int(rng.integers(0, 20)),
                                         "id2": int(rng.integers(0, 20))}),
           ("Qt1", Q.QT["Qt1"], lambda: None)]
    stream = []
    for _ in range(N_REQUESTS):
        name, text, draw = mix[int(rng.integers(0, len(mix)))]
        stream.append((name, text, draw()))

    # sequential references (doubles as per-binding warmup)
    pqs = {name: gopt.prepare(text, backend=args.backend)
           for name, text, _p in stream}
    ref = {}
    for name, _t, params in stream:
        k = (name, tuple(sorted((params or {}).items())))
        if k not in ref:
            ref[k] = pqs[name].execute(params)[0]

    srv = gopt.serve(backend=args.backend, max_wave=MAX_WAVE,
                     max_pending=N_REQUESTS + 1)
    # two warm epochs (fused-chain capacity growth recompiles once), then
    # the measured epoch re-forms the same waves fully warm
    for _ in range(2):
        for name, text, params in stream:
            srv.submit(text, params)
        srv.drain()
    srv.stats = ServeStats()

    reqs = [(name, srv.submit(text, params))
            for name, text, params in stream]
    srv.drain()
    srv.close()

    check(all(r.status == "done" for _, r in reqs),
          "not every request completed")
    bad = [f"{name}{r.params}" for name, r in reqs
           if not tables_equal(
               r.table, ref[(name, tuple(sorted((r.params or {}).items())))])]
    check(not bad, f"batched results differ from sequential: {bad[:5]}")

    s = srv.stats.summary()
    check(s["completed"] == N_REQUESTS, f"completed {s['completed']}")
    p99 = s["latency_p99_ms"]
    check(math.isfinite(p99) and 0 < p99 < 60_000,
          f"p99 latency out of bounds: {p99}ms")
    warm_chain = srv.stats.kernels.get("compile:fused_chain", 0)
    check(warm_chain == 0,
          f"warmed server compiled {warm_chain} fused-chain program(s)")
    print(f"serve smoke OK: {s['completed']} requests over {s['waves']} "
          f"waves (mean={s['mean_wave_size']:.1f}, "
          f"deduped={s['deduped']}), p50={s['latency_p50_ms']:.0f}ms "
          f"p99={p99:.0f}ms, warm chain compiles=0")


if __name__ == "__main__":
    main()
