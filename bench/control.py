#!/usr/bin/env python3
"""The control of ``correct``: the reference put in the program's place,
with one guarantee of the configuration broken, must come out not
correct.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--requests N]

The configurations state exact answers.  The control answers the requests
of a window (the cell's own data set and traffic, from each seed: an open
loop's schedule at the cell's run length, a closed loop's first
``--requests``, as many as a run of the cell answers) with the reference
computed under the mix's ``control`` (``bench/reference.py``: property
columns read through float32, or adjacency lists cut to a fixed width),
and serves its tables as the program would.  Each is held to the exact
reference by the same comparison ``bench/run.py`` makes.  It prints, per
seed, the tables the control gets wrong (the upper reading of
``tables_wrong``).  Host work only; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import compare  # noqa: E402
import datagen  # noqa: E402
import reference  # noqa: E402
import run as bench  # noqa: E402
import traffic  # noqa: E402

CLOSED_REQUESTS = 2000


def served_table(answer, result: dict) -> tuple:
    """``(cols, nrows)`` of the table a server would send for ``answer``."""
    if "columns" in result:
        rows = list(answer)
        return {c: np.array([r[i] for r in rows], np.int64)
                for i, c in enumerate(result["columns"])}, len(rows)
    keys = result.get("keys") or []
    if not keys:
        return {result["value"]: np.array([answer.get((), 0)])}, 1
    sign = -1 if result.get("order", "desc") == "desc" else 1
    rows = sorted(answer.items(), key=lambda kv: (sign * kv[1], kv[0]))
    rows = rows[:result["limit"]]
    cols = {k: np.array([key[i] for key, _ in rows], np.int64)
            for i, k in enumerate(keys)}
    cols[result["value"]] = np.array([v for _, v in rows], np.int64)
    return cols, len(rows)


def window(cfg: dict, mix: dict, seed: int, seconds: float,
           requests: int) -> tuple:
    """The data set and the window's requests of one seed, as a run of the
    cell draws them (warm-up bindings left out of the window)."""
    data = datagen.generate(cfg, seed)
    sizes = range(1, bench.largest_wave(mix, bench.server_max_wave()) + 1)
    warm = traffic.warmup_bindings(mix, data["n"], seed,
                                   bench.WARM_PASSES * len(sizes))
    reqs = bench.window_requests(mix, data["n"], seed, seconds, warm)
    return data, reqs[:requests]


def control_readings(cfg: dict, mix: dict, seed: int, seconds: float,
                     requests: int = CLOSED_REQUESTS,
                     control: dict | None = None) -> dict:
    """One window's requests answered by the control and held to the
    exact reference."""
    data, reqs = window(cfg, mix, seed, seconds, requests)
    exact = reference.Graph(data)
    broken = reference.Graph(data, mix["control"] if control is None
                             else control)
    refs = {q["reference"]: reference.load(q["reference"])
            for q in mix["queries"]}
    wrong = 0
    per_query: dict = {}
    for r in reqs:
        q = mix["queries"][r.query]
        ref = refs[q["reference"]]
        cols, nrows = served_table(ref(broken, r.params), q["result"])
        bad = compare.check_table(cols, nrows, ref(exact, r.params),
                                  q["result"]) is not None
        wrong += bad
        per_query[q["name"]] = per_query.get(q["name"], 0) + bad
    return {"seed": seed, "requests": len(reqs), "tables_wrong": wrong,
            "wrong_per_query": per_query}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=CLOSED_REQUESTS)
    args = ap.parse_args(argv)
    root = HERE.parent
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in spec["workloads"]}[args.workload]
    cfg_file = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((root / cfg_file["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    for s in args.seeds.split(","):
        print(json.dumps(control_readings(cfg, mix, int(s),
                                          spec["run_seconds"],
                                          args.requests)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
