#!/usr/bin/env python3
"""Chip smoke test: the served graph-query path on a TPU, at a real store size.

    python chip_smoke.py              # one chip: the jax backend
    python chip_smoke.py --chips 4    # four chips: the sharded backend only

Everything runs in this one process, through the entry points a user calls:
``generate_ldbc_streamed`` builds the store, one ``GOpt`` plans, and
``GOpt.serve`` answers about 64 requests through the ``QueryServer``
(``execute_many`` waves, fused chains, the Pallas WCOJ kernel and the
device relational tail).  The requests are served three times: two set-up
passes (uploads, compiles, the fused chains' measuring runs) and one warm
pass.  Every served table is compared row for row with the numpy backend.
The run fails if JAX finds no TPU, if the Pallas kernels would run in
interpret mode, if any result differs, or if anything fell back or
degraded: a request not ``done``, a retry, a bisection, a breaker trip, a
ladder level above 0, an engine fallback other than ``chain_capacity``, no
fused-chain dispatch or no WCOJ kernel call.

The lines before the last report what this run measured on the device it
names; none of them is a benchmark metric.  The last line is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE / "src"), str(HERE)]

# LDBC SNB SF1 order of magnitude: ~1.7M vertices, ~13.5M edges
DEFAULT_SF = 100.0
IC_READS = ("ic1", "ic3", "ic11", "ic12")
# cyclic CBO reads (benchmarks.queries.QC) that probe with the WCOJ kernel:
# Qc2b through the per-operator intersect, Qc4b as a fused chain's WCOJ
# tail.  Their whole-graph counts exceed max_rows at this scale, so each is
# anchored by a bound id: (query, anchor predicate, anchor type, parameter)
WCOJ_READS = (("Qc2b", "person1.id = $pid", "PERSON", "pid"),
              ("Qc4b", "forum.id = $fid", "FORUM", "fid"))
WCOJ_PER_QUERY = 4
# the only engine fallback a healthy run may record: a fused chain whose
# capacity schedule grew re-runs that execution on the per-hop loop
ALLOWED_FALLBACKS = {"chain_capacity"}
CONTAINMENT_COUNTERS = ("failed", "retries", "bisections", "breaker_trips",
                        "dropped", "rejected", "quarantined", "cancelled",
                        "deadline_aborts", "worker_respawns")


class SmokeFailure(Exception):
    """A phase of the smoke test failed."""


def require_tpu(chips: int) -> dict:
    """The device check: JAX's devices must be TPUs, at least ``chips``."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise SmokeFailure(f"no TPU: JAX's first device is "
                           f"{info['platform']} ({info['kind']})")
    if info["count"] < chips:
        raise SmokeFailure(f"--chips {chips} needs {chips} TPU devices; "
                           f"JAX sees {info['count']}")
    return info


def require_compiled(ops):
    """The jax operator set must run its Pallas kernels compiled."""
    if ops._interpret:
        raise SmokeFailure("the jax operator set would run its Pallas "
                           "kernels in interpret mode")


def build_requests(store, seed: int, n_requests: int, wcoj: bool) -> list:
    """The request mix: IC reads anchored at non-hub persons, plus (when
    ``wcoj``) the anchored cyclic reads.  Zipf hubs sit at the low ids of
    every type (``ldbc._zipf_targets``), so anchors come from the upper
    half of each id range."""
    import numpy as np

    from benchmarks import queries as Q
    rng = np.random.default_rng(seed)

    def anchors(vtype, k):
        n = store.v_count[vtype]
        return [int(x) for x in rng.choice(np.arange(n // 2, n), size=k,
                                           replace=False)]

    reqs = []
    if wcoj:
        for name, cond, vtype, param in WCOJ_READS:
            text = Q.QC[name].replace(" Return", f" Where {cond} Return")
            reqs += [(name, text, {param: a})
                     for a in anchors(vtype, WCOJ_PER_QUERY)]
    per_ic = max(1, (n_requests - len(reqs)) // len(IC_READS))
    for name in IC_READS:
        reqs += [(name, Q.QIC[name], {"pid": a})
                 for a in anchors("PERSON", per_ic)]
    return reqs


def _tables_equal(a, b) -> bool:
    import numpy as np
    return (a.nrows == b.nrows and set(a.cols) == set(b.cols)
            and all(np.array_equal(np.asarray(a.cols[k]),
                                   np.asarray(b.cols[k])) for k in a.cols))


def serve_and_check(gopt, backend: str, requests: list, refs: list,
                    passes: int = 3) -> dict:
    """Serve ``requests`` ``passes`` times through one ``QueryServer``
    (the last pass is the warm one) and hold every pass to the reference
    tables and the no-fallback rules.  Returns what was observed."""
    srv = gopt.serve(backend=backend)
    exchanges: set = set()
    mid_plan_d2h = 0
    walls, compiles = [], []
    from repro.core.physical_spec import TransferStats
    try:
        for _ in range(passes):
            n_waves = len(srv.stats.wave_compiles)
            t0 = time.perf_counter()
            handles = [srv.submit(text, params)
                       for _, text, params in requests]
            srv.drain()
            walls.append(time.perf_counter() - t0)
            compiles.append(sum(srv.stats.wave_compiles[n_waves:]))
            for h, (name, _, params), ref in zip(handles, requests, refs):
                if h.status != "done":
                    raise SmokeFailure(f"{name}{params}: request ended "
                                       f"{h.status}: {h.error}")
                if not _tables_equal(h.table, ref):
                    raise SmokeFailure(f"{name}{params}: served table "
                                       f"differs from the numpy reference")
                bad = set(h.stats.fallbacks) - ALLOWED_FALLBACKS
                if bad:
                    raise SmokeFailure(f"{name}{params}: fell back "
                                       f"({h.stats.fallbacks})")
                exchanges.update(h.stats.exchanges or {})
                mid_plan_d2h += TransferStats.mid_plan_d2h(h.stats.transfers)
    finally:
        srv.close()
    s = srv.stats.summary()
    moved = {k: s[k] for k in CONTAINMENT_COUNTERS if s[k]}
    if moved:
        raise SmokeFailure(f"the serving ladder moved: {moved}")
    levels = {b["level"] for b in srv._breakers.values()}
    if levels - {0}:
        raise SmokeFailure(f"a plan ran degraded (ladder levels {levels})")
    bad = set(s["fallbacks"]) - ALLOWED_FALLBACKS
    if bad:
        raise SmokeFailure(f"engine fallbacks recorded: {s['fallbacks']}")
    return {"walls": walls, "compiles": compiles, "kernels": s["kernels"],
            "exchanges": sorted(exchanges), "mid_plan_d2h": mid_plan_d2h,
            "waves": s["waves"], "fallbacks": s["fallbacks"]}


def run(args, log) -> None:
    """Every phase after the device check."""
    from repro.compile_cache import enable_compile_cache
    from repro.core.gopt import GOpt
    from repro.graphdb.ldbc import generate_ldbc_streamed

    log(f"compile cache: {enable_compile_cache()}")
    sharded = args.chips > 1
    t0 = time.perf_counter()
    store = generate_ldbc_streamed(sf=args.sf, seed=args.seed)
    t_store = time.perf_counter() - t0
    log(f"store: sf={args.sf} seed={args.seed} {store.n_vertices} vertices "
        f"{store.n_edges} edges, built in {t_store:.3f} s (host)")
    if args.sf < DEFAULT_SF:
        log(f"cut: sf={args.sf} instead of {DEFAULT_SF}")

    t0 = time.perf_counter()
    if sharded:
        gopt = GOpt(store, backend="sharded", devices=args.chips)
    else:
        gopt = GOpt(store, backend="jax")
    t_gopt = time.perf_counter() - t0
    log(f"GOpt (statistics + GLogue): {t_gopt:.3f} s (host)")
    ops = gopt.spec.operators(store)
    require_compiled(ops)
    if sharded and ops.n_shards != args.chips:
        raise SmokeFailure(f"sharded backend built {ops.n_shards} shards, "
                           f"not {args.chips}")

    requests = build_requests(store, args.seed, args.requests,
                              wcoj=not sharded)
    t0 = time.perf_counter()
    refs = [gopt.run(text, params, backend="numpy")[0]
            for _, text, params in requests]
    log(f"numpy reference: {len(requests)} requests in "
        f"{time.perf_counter() - t0:.3f} s (host)")

    out = serve_and_check(gopt, gopt.spec.name, requests, refs)
    k = out["kernels"]
    log(f"served {len(requests)} requests x {len(out['walls'])} passes on "
        f"{gopt.spec.name}: set-up passes (uploads, compiles) "
        + ", ".join(f"{w:.3f} s" for w in out["walls"][:-1])
        + f"; warm pass {out['walls'][-1]:.3f} s; compiles per pass "
        f"{out['compiles']}; {out['waves']} waves")
    log("kernel dispatches (all passes): " + json.dumps(
        dict(sorted(k.items())), sort_keys=True))
    if out["fallbacks"]:
        log(f"allowed fallbacks: {out['fallbacks']}")
    if sharded:
        log(f"collectives: {out['exchanges']}; mid-plan d2h "
            f"{out['mid_plan_d2h']}; {ops.n_shards} shards")
        if not out["exchanges"]:
            raise SmokeFailure("the sharded backend recorded no exchange")
        if out["mid_plan_d2h"]:
            raise SmokeFailure(f"{out['mid_plan_d2h']} mid-plan device->host "
                               f"transfers")
    else:          # the sharded backend runs chains on its per-hop loop
        if not k.get("dispatch:fused_chain"):
            raise SmokeFailure("no fused-chain dispatch")
        if not k.get("dispatch:wcoj"):
            raise SmokeFailure("the WCOJ kernel never ran")
    import jax
    mem = jax.devices()[0].memory_stats() or {}
    log(f"device memory: bytes_in_use={mem.get('bytes_in_use', 'n/a')} "
        f"peak_bytes_in_use={mem.get('peak_bytes_in_use', 'n/a')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: serve through the sharded backend on a "
                         "4-chip mesh (and run nothing else)")
    ap.add_argument("--sf", type=float, default=DEFAULT_SF,
                    help="scale factor of the streamed LDBC-like store")
    ap.add_argument("--seed", type=int, default=7,
                    help="seed of the store and of the request anchors")
    ap.add_argument("--requests", type=int, default=64)
    args = ap.parse_args(argv)
    try:
        info = require_tpu(args.chips)
        tag = f"[{info['platform']} {info['kind']} x{info['count']}]"

        def log(msg):
            print(f"chip_smoke {tag} {msg}", flush=True)

        t0 = time.perf_counter()
        run(args, log)
        log(f"total {time.perf_counter() - t0:.3f} s")
    except SmokeFailure as exc:
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
