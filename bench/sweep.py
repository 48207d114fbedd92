#!/usr/bin/env python3
"""Find a cell's knee: one process, one set-up, then a window at each
load, lightest first.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --loads 1,2,4,8

A load is an offered rate in requests per second for an open-loop cell,
or a number of clients for a closed-loop one (set-up then warms waves up
to the largest).  For each load it prints one line: offered and completed
requests per second, p50 and p90 latency from the send time, the
requests still unanswered when the window closed (the queue's growth),
and the compiles inside the window.  An open loop's knee is the highest
rate whose ``completed_qps`` keeps up with the offered rate with no queue
growing through the window, and its traffic file then carries 0.8 times
it; a closed loop's throughput stops growing with clients at its knee.
An open-loop sweep stops after the first rate at which more than a tenth
of the window's requests are still open when it closes.  Needs the chip,
like ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as bench
import stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--loads", required=True,
                    help="comma-separated offered rates (requests/s) or "
                         "client counts")
    args = ap.parse_args(argv)
    try:
        run = bench.load_cell(bench.ROOT, args.workload)
        bench.configure_jax(bench.ROOT)
        device = bench.require_tpu(run["cell"]["chips"])
    except (bench.BenchError, OSError, KeyError, ValueError) as exc:
        print(f"sweep FAILED: {exc}", file=sys.stderr, flush=True)
        return 3
    tag = f"[{device['platform']} {device['kind']} x{device['count']}]"

    def log(msg):
        print(f"sweep {args.workload} {tag} {msg}", flush=True)

    mix = run["mix"]
    closed = mix["arrivals"]["process"] == "closed"
    loads = [float(x) for x in args.loads.split(",")]
    if closed:
        mix["arrivals"]["clients"] = int(max(loads))
    counter = bench.CompileCount()
    st = bench.set_up(run, args.seed, args.seconds, log, counter)
    rows = []
    for i, load in enumerate(loads):
        if closed:
            mix["arrivals"]["clients"] = int(load)
            reqs = st["window"][i * bench.CLOSED_STREAM // len(loads):]
        else:
            mix["arrivals"] = {"process": "poisson", "rate": load}
            reqs = bench.window_requests(mix, st["store"].v_count,
                                         args.seed + i, args.seconds,
                                         st["warm"])
        c0 = counter.mark()
        names0 = dict(counter.names)
        out = bench.drive_window(st["srv"], st["plans"], mix, reqs,
                                 args.seconds)
        c1 = counter.mark()
        recs = out["records"]
        e2e = stats.end_to_end(recs, out["window_s"])
        open_at_close = sum(1 for r in recs
                            if r.host_s is None or r.host_s > args.seconds)
        row = {"load": load, "sent": len(recs),
               "completed_qps": e2e["completed_qps"],
               "p50_ms": e2e["p50_ms"], "p90_ms": e2e["p90_ms"],
               "open_at_close": open_at_close,
               "failed": sum(1 for r in recs if bench.failed_reasons(
                   r, out["marks"]["ladder_steps"])),
               "xla_compiles": c1[0] - c0[0],
               "cache_loads": c1[1] - c0[1],
               "waves": len(st["srv"].stats.exec_s) - out["marks"]["waves"]}
        rows.append(row)
        log(json.dumps(row))
        log(f"compiled in the window: {counter.names_since(names0)}")
        # the queue grows through the window: past the knee
        if not closed and open_at_close > max(2, 0.1 * len(recs)):
            break
    st["srv"].close()
    print(json.dumps({"sweep": rows, "device": device,
                      "setup": st["phases"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
