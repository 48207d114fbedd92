"""Parent/change pairs of one benchmark cell on one machine.

Runs ``bench/run.py`` from two checkouts, the tree this script sits in
(``change``) and ``--parent``, once each per seed, in the order parent,
change, change, parent, ... so that neither side always runs first.
Each run's result line goes to ``--out`` (JSON lines) with its side,
seed and the end of its log (window compiles, fallbacks).  With ``--trace 1`` each run also splits its trace by the
program's spans: the idle seconds and the count of each ``gopt.op.*`` and
``gopt.sync.*`` span inside the window's waves, and how many
``gopt.sync.refilter`` spans each wave with a ``gopt.op.BATCH_BIND``
holds.

    python3 scripts/bench_pairs.py --parent <dir> --workload <cell> \\
        --seconds 20 --trace 0 --seeds 11 12 13 14 --out pairs.jsonl

The script itself stays off JAX (a chip belongs to one process): the
trace split runs in a child with ``JAX_PLATFORMS=cpu``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from collections import Counter
from pathlib import Path

CHANGE = Path(__file__).resolve().parent.parent


def span_counts(root: str) -> dict:
    """The newest trace under ``<root>/.bench_trace``, split by span."""
    sys.path[:0] = [str(Path(root) / "bench")]
    import span_reduce
    import trace_reduce
    path = max(Path(root, span_reduce.TRACE_DIR).glob(
        "*/plugins/profile/*/*.xplane.pb"), key=os.path.getmtime).as_posix()
    out = span_reduce.split(path)
    _, spans = span_reduce.read_events(path)
    w0, w1 = next((s, e) for _, n, s, e in spans
                  if n == trace_reduce.WINDOW_SPAN)
    waves = [x for x in spans if x[1] == "gopt.wave" and x[3] > w0
             and x[2] < w1]
    inside = span_reduce._in_waves([x for x in spans if x[1].startswith(
        ("gopt.sync.", "gopt.op."))], waves)
    out["span_counts"] = dict(Counter(x[1] for x in inside))
    per_wave = []
    for th, _, s, e in waves:
        names = [x[1] for x in inside if x[0] == th and s <= x[2] < e]
        if "gopt.op.BATCH_BIND" in names:
            per_wave.append(names.count("gopt.sync.refilter"))
    out["refilter_per_stacked_wave"] = dict(Counter(per_wave))
    del out["idle_gaps"]
    return out


def run(side: str, root: Path, args, seed: int) -> dict:
    cmd = [sys.executable, "bench/run.py", "--workload", args.workload,
           "--seed", str(seed), "--seconds", str(args.seconds),
           "--trace", str(args.trace)]
    p = subprocess.run(cmd, cwd=root, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    rec = {"side": side, "seed": seed, "rc": p.returncode,
           "stderr": p.stderr[-3000:]}
    try:
        rec["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        pass
    if args.trace and p.returncode == 0:
        s = subprocess.run(
            [sys.executable, __file__, "--spans", str(root)],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        rec["spans"] = (json.loads(s.stdout) if s.returncode == 0
                        else s.stderr[-2000:])
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--spans", help="print span_counts(<root>) and exit")
    ap.add_argument("--parent")
    ap.add_argument("--workload")
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seeds", type=int, nargs="+")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    if args.spans:
        print(json.dumps(span_counts(args.spans), sort_keys=True))
        return 0
    sides = [("parent", Path(args.parent).resolve()), ("change", CHANGE)]
    rc = 0
    with open(args.out, "a") as f:
        for i, seed in enumerate(args.seeds):
            for side, root in (sides if i % 2 == 0 else sides[::-1]):
                rec = run(side, root, args, seed)
                rc |= rec["rc"] != 0
                f.write(json.dumps(rec) + "\n")
                f.flush()
                res = rec.get("result", {})
                print(side, seed, rec["rc"], res.get("correct"),
                      res.get("failed"),
                      {k: v["value"] for k, v in
                       res.get("metrics", {}).items()}, flush=True)
    return int(rc)


if __name__ == "__main__":
    raise SystemExit(main())
