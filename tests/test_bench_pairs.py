"""``scripts/bench_pairs.py``: the sides alternate which runs first, every
run's record lands in the output, and the span split reads a trace."""
import importlib.util
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
spec = importlib.util.spec_from_file_location(
    "bench_pairs", REPO / "scripts" / "bench_pairs.py")
bp = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bp)


def test_sides_alternate_and_every_run_is_recorded(tmp_path, monkeypatch):
    order = []

    def fake_run(side, root, args, seed):
        order.append((side, seed))
        return {"side": side, "seed": seed, "rc": 0,
                "result": {"correct": True, "failed": 0,
                           "metrics": {"completed_qps": {"value": 1.0}}}}

    monkeypatch.setattr(bp, "run", fake_run)
    out = tmp_path / "pairs.jsonl"
    assert bp.main(["--parent", str(tmp_path), "--workload", "w",
                    "--seeds", "7", "8", "9", "--out", str(out)]) == 0
    assert order == [("parent", 7), ("change", 7), ("change", 8),
                     ("parent", 8), ("parent", 9), ("change", 9)]
    recs = [json.loads(line) for line in out.read_text().splitlines()]
    assert [(r["side"], r["seed"]) for r in recs] == order


def test_span_counts_reads_a_trace_without_program_spans(tmp_path):
    prof = tmp_path / ".bench_trace" / "cell" / "plugins" / "profile" / "t"
    prof.mkdir(parents=True)
    shutil.copy(REPO / "bench" / "tests" / "data" / "cpu_window.xplane.pb",
                prof)
    (tmp_path / "bench").symlink_to(REPO / "bench")
    sp = bp.span_counts(str(tmp_path))
    assert sp["waves"] == 0 and sp["span_counts"] == {}
    assert sp["refilter_per_stacked_wave"] == {}
    assert sp["window_s"] > 0
