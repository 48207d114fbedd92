"""The harness end to end on the CPU, at a size a test run holds, with the
look for a chip steered here in the test:

- a configuration, a traffic mix and a per-layer metric added as files
  plus ``BENCHMARK.json`` entries alone are found by name and run;
- with the timed path broken underneath (an answer altered where it is
  produced; half of each wave's requests left out and answered with
  another's table), ``correct`` comes out false: the closed loop's clients
  coalesce same-plan requests into waves of several;
- ``bench/run.py`` exits non-zero, with no result, on a CPU device and in
  a checkout that holds only the benchmark.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import run as bench

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
CELL = "tiny.tiny_mix"
SEED = 2**31 + 99


def add_cell(root: Path):
    """A new configuration, mix and metric, as files and entries only."""
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "snb_sf0.1.json").read_text())
    cfg.update(name="tiny", sf=0.03)
    mix = json.loads((BENCH / "traffic" / "is_closed.json").read_text())
    ic = json.loads((BENCH / "traffic" / "ic_open.json").read_text())
    mix["queries"] += [q for q in ic["queries"] if q["name"] == "ic11"]
    # fresh bindings and a closed loop of four clients: the server
    # coalescing, deduplicating and padding same-plan requests
    mix.update(name="tiny_mix", arrivals={"process": "closed",
                                          "clients": 4})
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    (root / "bench" / "metrics").mkdir(parents=True)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny_mix.json").write_text(
        json.dumps(mix))
    (root / "bench" / "metrics" / "tiny_answered.py").write_text(
        "def read(run):\n"
        "    return sum(r.host_s is not None for r in run['records'])\n")
    b["configs"].append({"name": "tiny", "source": "a test",
                         "file": "bench/configs/tiny.json",
                         "reduced": ["sf"], "why": "a test"})
    b["workloads"].append({"name": CELL, "config": "tiny",
                           "traffic": "tiny_mix", "chips": 1,
                           "why": "a test"})
    b["per_layer"].append({"name": "tiny_answered", "unit": "req",
                           "better": "higher", "source": "host_clock",
                           "layer": "load generator", "moves": "p50_ms",
                           "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    add_cell(root)
    return bench.load_cell(root, CELL)


def test_new_files_are_found_by_name(cell):
    assert cell["config"]["sf"] == 0.03
    assert cell["mix"]["name"] == "tiny_mix"
    assert [m["name"] for m in cell["per_layer"]] == ["tiny_answered"]
    # metrics that name their cells leave the new cell out
    assert {m["name"] for m in cell["end_to_end"]} == \
        {"completed_qps", "setup_s"}
    read = bench.load_reader(cell["root"], "tiny_answered")
    assert read({"records": []}) == 0


def window(cell, st, trace=False):
    st = dict(st, srv=st["gopt"].serve(backend="jax"))
    return bench.measure(cell, st, 2.0, trace,
                         {"platform": "cpu", "kind": "cpu", "count": 1},
                         lambda msg: None, bench.CompileCount())


@pytest.fixture(scope="module")
def setup_state(cell):
    mp = pytest.MonkeyPatch()
    mp.setattr(bench, "require_compiled", lambda ops: None)
    try:
        yield bench.set_up(cell, SEED, 2.0, lambda msg: None)
    finally:
        mp.undo()


def test_added_cell_runs_and_is_correct(cell, setup_state):
    res = window(cell, setup_state)
    assert res["correct"] is True and res["failed"] == 0
    # every client sent at least once; a loaded test host sends fewer
    assert res["attempted"] >= cell["mix"]["arrivals"]["clients"]
    assert set(res["metrics"]) == {"completed_qps", "setup_s"}
    assert list(res)[-1] == "checks"


def test_added_metric_is_reported_in_a_traced_run(cell, setup_state):
    res = window(cell, setup_state, trace=True)
    assert res["correct"] is True
    assert res["metrics"]["tiny_answered"]["value"] == res["attempted"]
    assert res["metrics"]["tiny_answered"]["unit"] == "req"
    assert "breakdown" in res and "window_s" in res["device"]


def alter_answer(orig):
    """A fault: the last column's first value, one off, as produced."""
    def exec_group(self, pq, reqs, exec_kw, level):
        orig(self, pq, reqs, exec_kw, level)
        for r in reqs:
            cols = dict(r.table.cols)
            last = list(cols)[-1]
            v = np.array(cols[last])
            v[0] += 1
            cols[last] = v
            r.table = type(r.table)(cols, r.table.nrows, r.table.ops)
    return exec_group


def drop_half(orig):
    """A fault: half of each wave left out, answered with another's table."""
    def exec_group(self, pq, reqs, exec_kw, level):
        keep = reqs[:max(1, len(reqs) // 2)]
        orig(self, pq, keep, exec_kw, level)
        for r in reqs[len(keep):]:
            r.table, r.stats = keep[0].table, keep[0].stats
            r.status, r.finish_s = "done", keep[0].finish_s
    return exec_group


@pytest.mark.parametrize("fault", [alter_answer, drop_half])
def test_broken_timed_path_is_not_correct(cell, setup_state, fault,
                                          monkeypatch):
    from repro.graphdb import serve
    monkeypatch.setattr(serve.QueryServer, "_exec_group",
                        fault(serve.QueryServer._exec_group))
    res = window(cell, setup_state)
    assert res["correct"] is False
    assert res["checks"]["tables_wrong"]["value"] > 0


def run_cli(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "snb_sf0.1.is_closed",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_on_a_cpu_device():
    p = run_cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "program" in p.stderr
