"""``BENCHMARK.json`` is well formed, and every name in it resolves to
the files of its own that the harness reads."""
import json
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["bench"]
    assert 1 <= B["run_seconds"] <= 51


@pytest.mark.parametrize("part", sorted(KEYS))
def test_entries(part):
    names = [e["name"] for e in B[part]]
    assert len(names) == len(set(names))
    for e in B[part]:
        extra = set(e) - KEYS[part] - ({"workloads"} if part in
                                        ("end_to_end", "per_layer") else set())
        assert set(e) >= KEYS[part] and not extra, e
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")


def test_cells_resolve_to_files():
    configs = {c["name"]: c for c in B["configs"]}
    for c in B["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
    for w in B["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in B["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in B["end_to_end"]}


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in B["workloads"]:
        def mine(m):
            return w["name"] in m.get("workloads", [w["name"]])
        e2e = {m["name"] for m in B["end_to_end"] if mine(m)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(mine(m) for m in B["per_layer"])


def test_bounds():
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
