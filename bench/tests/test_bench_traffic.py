"""The one traffic generator: seeded repeats, exact shares, the arrival
processes."""
import json
from pathlib import Path

import numpy as np
import pytest

import traffic

BENCH = Path(__file__).resolve().parents[1]
V_COUNT = {"PERSON": 54000, "FORUM": 27000}
SEED = 2**31 + 12345          # past 32 signed bits, as the driver's are


def mix(name="ic_open"):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["ic_open", "cgp_open"])
def test_schedule_repeats_per_seed(name):
    a = traffic.schedule(mix(name), V_COUNT, SEED, 30.0)
    b = traffic.schedule(mix(name), V_COUNT, SEED, 30.0)
    assert [(r.due_s, r.query, r.params) for r in a] == \
        [(r.due_s, r.query, r.params) for r in b]
    c = traffic.schedule(mix(name), V_COUNT, SEED + 1, 30.0)
    assert [r.due_s for r in a] != [r.due_s for r in c]
    assert [r.params for r in a] != [r.params for r in c]


@pytest.mark.parametrize("seed", [0, 7, SEED])
def test_every_seed_offers_the_same_work(seed):
    m = mix()
    rate = m["arrivals"]["rate"]
    reqs = traffic.schedule(m, V_COUNT, seed, 30.0)
    assert len(reqs) == round(rate * 30.0)
    counts = np.bincount([r.query for r in reqs],
                         minlength=len(m["queries"]))
    assert counts.max() - counts.min() <= 1          # equal shares
    times = [r.due_s for r in reqs]
    assert times == sorted(times) and 0.0 <= times[0] and times[-1] < 30.0


def test_anchors_stay_in_their_range():
    m = mix()
    for r in traffic.schedule(m, V_COUNT, SEED, 30.0):
        pid = r.params["pid"]
        assert V_COUNT["PERSON"] // 2 <= pid < V_COUNT["PERSON"]


def test_zipf_anchors_skew_to_the_range_start():
    q = {"anchors": {"pid": {"type": "PERSON", "dist": "zipf", "a": 1.5,
                             "range": [0.5, 1.0]}}}
    rng = traffic.rng_for(SEED, traffic.WINDOW)
    ids = [traffic.draw_params(rng, q, V_COUNT)["pid"] for _ in range(2000)]
    lo = V_COUNT["PERSON"] // 2
    assert min(ids) >= lo and max(ids) < V_COUNT["PERSON"]
    assert sum(i == lo for i in ids) > 0.2 * len(ids)


def test_onoff_sends_only_in_on_periods():
    arr = {"process": "onoff", "rate": 4.0, "on_s": 2.0, "off_s": 3.0}
    t = traffic.arrival_times(traffic.rng_for(SEED, 0), arr, 30.0)
    assert len(t) == 120
    assert all((x % 5.0) < 2.0 for x in t)


def test_closed_stream_and_warmup_bindings():
    m = mix()
    s = traffic.closed_stream(m, V_COUNT, SEED, 64)
    assert len(s) == 64
    assert [r.params for r in s] == \
        [r.params for r in traffic.closed_stream(m, V_COUNT, SEED, 64)]
    warm = traffic.warmup_bindings(m, V_COUNT, SEED, 16)
    window = {json.dumps(r.params) for r in traffic.schedule(
        m, V_COUNT, SEED, 30.0)}
    for bs in warm:
        assert len(bs) == 16
        assert len({json.dumps(p) for p in bs}) == 16
        # drawn from their own stream, not the window's
        assert {json.dumps(p) for p in bs} != window


@pytest.mark.parametrize("closed", [False, True])
def test_the_window_never_sends_a_warmup_binding(closed):
    m = mix()
    small = {"PERSON": 40, "FORUM": 40}
    warm = traffic.warmup_bindings(m, small, SEED, 12)
    used = [{traffic.binding_key(p) for p in bs} for bs in warm]
    if closed:
        reqs = traffic.closed_stream(m, small, SEED, 200, used)
    else:
        reqs = traffic.schedule(m, small, SEED, 60.0, used)
    assert len(reqs) >= 60
    for r in reqs:
        assert traffic.binding_key(r.params) not in used[r.query]
    # the same seed draws the same window around the same warm-up
    again = traffic.schedule(m, small, SEED, 60.0, used) if not closed \
        else traffic.closed_stream(m, small, SEED, 200, used)
    assert [r.params for r in again] == [r.params for r in reqs]


def test_fresh_bindings_without_a_pool():
    m = mix()
    reqs = traffic.schedule(m, V_COUNT, SEED, 30.0)
    assert len({json.dumps(r.params) for r in reqs}) > len(reqs) // 2
