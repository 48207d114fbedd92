"""The control of ``correct`` at a size a test run holds: the reference
under each mix's control (property columns through float32 for the short
reads; adjacency lists cut to the WCOJ kernel's ELL width for the IC and
cyclic mixes), an inexact answer breaking the configurations'
exact-answer guarantee, must fail the comparison, and the exact reference
put in the same place must pass it.  The generator's counts are exact for
every seed, and the reference itself is held to the program's plain numpy
backend at a small size where both are cheap."""
import json
from pathlib import Path

import numpy as np
import pytest

import compare
import control
import datagen
import reference
import traffic

BENCH = Path(__file__).resolve().parents[1]
SEED = 2**31 + 7


MIXES = ["ic_open", "cgp_open", "is_closed"]


def load(mix, sf=0.1):
    cfg = json.loads((BENCH / "configs" / "snb_sf0.1.json").read_text())
    cfg["sf"] = sf         # hubs above the ELL width in the IC/CGP mixes
    return cfg, json.loads((BENCH / "traffic" / f"{mix}.json").read_text())


@pytest.mark.parametrize("mix", MIXES)
def test_control_is_not_correct(mix):
    cfg, m = load(mix)
    got = control.control_readings(cfg, m, SEED, 20.0, requests=200)
    assert got["tables_wrong"] > 0


@pytest.mark.parametrize("mix", MIXES)
def test_exact_reference_in_the_programs_place_is_correct(mix):
    cfg, m = load(mix)
    got = control.control_readings(cfg, m, SEED, 20.0, requests=200,
                                   control={})
    assert got["tables_wrong"] == 0


def test_comparison_accepts_any_order_of_ties():
    res = {"keys": ["friend"], "value": "c", "order": "desc", "limit": 2}
    answer = {(1,): 5, (2,): 3, (3,): 3, (4,): 1}
    for tied in (2, 3):
        cols = {"friend": np.array([1, tied]), "c": np.array([5, 3])}
        assert compare.check_table(cols, 2, answer, res) is None
    cols = {"friend": np.array([1, 4]), "c": np.array([5, 1])}
    assert compare.check_table(cols, 2, answer, res) is not None
    cols = {"friend": np.array([2, 1]), "c": np.array([3, 5])}
    assert compare.check_table(cols, 2, answer, res) is not None


@pytest.fixture(scope="module")
def small():
    cfg, _ = load("ic_open", sf=0.03)
    data = datagen.generate(cfg, SEED)
    return cfg, data, datagen.build_program_store(data)


def test_counts_are_exact_for_every_seed(small):
    cfg, data, store = small
    other = datagen.generate(cfg, SEED + 1)
    assert other["n"] == data["n"] == datagen.vertex_counts(cfg)
    for e in cfg["edges"]:
        t = tuple(e["triple"])
        src, dst = data["edges"][t]
        assert len(other["edges"][t][0]) == len(src)
        if e["src"] != "split":
            assert len(src) == datagen.edge_count(cfg, e, data["n"])
        # distinct, no self-loops: the store keeps every generated edge
        pairs = src * data["n"][t[2]] + dst
        assert len(np.unique(pairs)) == len(pairs)
        if t[0] == t[2]:
            assert not (src == dst).any()
    replies = [len(data["edges"][("COMMENT", "REPLYOF", d)][0])
               for d in ("POST", "COMMENT")]
    assert sum(replies) == data["n"]["COMMENT"]
    assert store.n_edges == sum(len(s) for s, _ in data["edges"].values())


@pytest.mark.parametrize("mix", MIXES)
def test_reference_agrees_with_the_programs_numpy_backend(small, mix):
    from repro.core.gopt import GOpt
    _, data, store = small
    g = reference.Graph(data)
    assert g.offset == store.v_offset
    gopt = GOpt(store, build_glogue=False)
    _, m = load(mix)
    if m["arrivals"]["process"] == "closed":
        reqs = traffic.closed_stream(m, data["n"], SEED, 24)
    else:
        reqs = traffic.schedule(m, data["n"], SEED, 10.0)[:24]
    for r in reqs:
        q = m["queries"][r.query]
        tb, _ = gopt.run(q["text"], r.params, backend="numpy")
        cols = {k: np.asarray(v) for k, v in tb.cols.items()}
        want = reference.load(q["reference"])(g, r.params)
        assert compare.check_table(cols, tb.nrows, want, q["result"]) \
            is None, (q["name"], r.params)
