#!/usr/bin/env python
"""Sharded-backend smoke for CI (scripts/ci.sh, DESIGN.md §10): on a
host-count-faked 8-device mesh, the mesh-partitioned backend must

  - pass the OperatorSet-v2 conformance suite (semantics + row-order
    contract + blow-up guard) unchanged,
  - run a 2-hop Appendix-A query row-identical to the numpy backend,
  - exchange frontiers with recorded on-device collectives
    (``ExchangeStats`` events > 0, ZERO mid-plan device->host transfers),
  - gather the binding table to the host exactly once, at delivery.

Usage: PYTHONPATH=src python scripts/sharded_smoke.py [--sf 0.05]
"""
import argparse
import os
import sys

# the faked mesh must exist before the FIRST jax import anywhere
if "jax" not in sys.modules:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

sys.path.insert(0, "src")
sys.path.insert(1, ".")

import numpy as np                                                 # noqa: E402

from benchmarks import queries as Q                                # noqa: E402
from repro.core.gopt import GOpt                                   # noqa: E402
from repro.core.physical_spec import (TransferStats,               # noqa: E402
                                      validate_operator_set)
from repro.graphdb.ldbc import generate_ldbc                       # noqa: E402

# ic1 is the 2-hop KNOWS*2 friend-of-foaf query (collective expansion +
# gathered tail); Qc1a closes a cycle through the psum-combined intersect
SMOKE = [("ic1", Q.QIC["ic1"], Q.QIC_PARAMS["ic1"]),
         ("Qc1a", Q.QC["Qc1a"], None)]


def check(cond, msg):
    if not cond:
        print(f"SHARDED SMOKE FAIL: {msg}")
        sys.exit(1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.05)
    args = ap.parse_args()
    import jax
    check(len(jax.devices()) >= 4,
          f"faked mesh has {len(jax.devices())} device(s); "
          f"XLA_FLAGS was set too late (jax imported first?)")

    store = generate_ldbc(sf=args.sf)
    gopt = GOpt(store, backend="sharded")
    ops = gopt.spec.operators(store)
    check(ops.n_shards >= 4, f"expected >=4 shards, got {ops.n_shards}")
    validate_operator_set(ops, conformance=True)   # raises on violation
    print(f"  ok conformance: {ops.n_shards}-shard mesh passes the "
          f"OperatorSet-v2 suite")

    for name, text, params in SMOKE:
        opt = gopt.optimize(text, params)
        ref, _ = gopt.execute(opt, backend="numpy")
        tbl, stats = gopt.execute(opt)
        check(tbl.nrows == ref.nrows and set(tbl.cols) == set(ref.cols)
              and all(np.array_equal(tbl.cols[k], ref.cols[k])
                      for k in tbl.cols),
              f"{name}: sharded result diverged from numpy")
        check(stats.exchanges, f"{name}: no collective exchanges recorded")
        check(TransferStats.mid_plan_d2h(stats.transfers) == 0,
              f"{name}: mid-plan device->host transfers: {stats.transfers}")
        delivered = stats.transfers.get("deliver:d2h", {}).get("calls", 0)
        check(tbl.nrows == 0 or delivered > 0,
              f"{name}: result not delivered through ops.to_host")
        ex_calls = sum(v["calls"] for v in stats.exchanges.values())
        print(f"  ok {name}: rows={tbl.nrows} exchanges={ex_calls} "
              f"deliver_d2h={delivered}")
    print("SHARDED SMOKE OK")


if __name__ == "__main__":
    main()
