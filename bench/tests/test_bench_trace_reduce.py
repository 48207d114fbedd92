"""The trace reduction, on a small recorded CPU trace (its numbers test
the arithmetic and are no device numbers) and on hand-made events."""
from pathlib import Path

import pytest

import trace_reduce as T

TRACE = Path(__file__).resolve().parent / "data" / "cpu_window.xplane.pb"


@pytest.fixture(scope="module")
def cpu():
    return T.reduce(str(TRACE), device_plane="/host:CPU")


def test_busy_and_idle_fill_the_window(cpu):
    assert 0.0 < cpu["busy_s"] < cpu["window_s"]
    idle = sum(s for _, s in cpu["idle_gaps"])
    assert idle + cpu["busy_s"] == pytest.approx(cpu["window_s"], rel=1e-9)


def test_device_time_per_module(cpu):
    names = dict(cpu["device_ops"])
    assert set(names) == {"jit__lambda"}
    assert "dot_general" in cpu["op_s"]
    # ops of one module on one thread never overlap: their sum is busy
    assert names["jit__lambda"] == pytest.approx(cpu["busy_s"], rel=1e-9)


def test_idle_gaps_are_named_by_the_open_span(cpu):
    gaps = dict(cpu["idle_gaps"])
    # three 20 ms sleeps inside bench.idle spans
    assert gaps["bench.idle"] >= 0.06
    assert gaps["bench.idle"] > 10 * gaps.get("bench.step", 0.0)


def fake(monkeypatch, modules, spans, ops=()):
    monkeypatch.setattr(T, "read_events",
                        lambda path, plane: (modules, list(ops), spans))
    return T.reduce("unused")


def test_union_of_overlapping_ops_and_clipping(monkeypatch):
    ms = 1e6
    spans = [("main", "bench.window", 10 * ms, 110 * ms),
             ("main", "bench.step", 10 * ms, 60 * ms),
             ("main", "bench.idle", 60 * ms, 110 * ms),
             ("gen", "bench.submit", 70 * ms, 75 * ms)]
    mods = [("/device:TPU:0", "mod_a", 0 * ms, 20 * ms),    # clipped
            ("/device:TPU:0", "mod_b", 15 * ms, 30 * ms),   # overlaps
            ("/device:TPU:0", "jit_run", 40 * ms, 50 * ms),
            ("/device:TPU:0", "mod_a", 100 * ms, 130 * ms)]  # clipped
    ops = [("/device:TPU:0", "%wcoj_intersect", 42 * ms, 45 * ms),
           ("/device:TPU:0", "%fusion", 45 * ms, 50 * ms),
           ("/device:TPU:0", "%wcoj_intersect", 105 * ms, 115 * ms)]
    r = fake(monkeypatch, mods, spans, ops)
    assert r["window_s"] == pytest.approx(0.100)
    # union: [10,30] + [40,50] + [100,110] = 40 ms
    assert r["busy_s"] == pytest.approx(0.040)
    assert dict(r["device_ops"]) == pytest.approx(
        {"mod_a": 0.020, "mod_b": 0.015, "jit_run": 0.010})
    # a kernel's time is its operations', inside the window
    assert r["op_s"] == pytest.approx({"%wcoj_intersect": 0.008,
                                       "%fusion": 0.005})
    gaps = dict(r["idle_gaps"])
    # [30,40] and [50,60] under bench.step; [60,100] under bench.idle,
    # where the loop's span wins over the generator's bench.submit
    assert gaps == pytest.approx({"bench.step": 0.020, "bench.idle": 0.040})


def test_gap_outside_loop_spans_takes_another_threads_span(monkeypatch):
    ms = 1e6
    spans = [("main", "bench.window", 0.0, 100 * ms),
             ("main", "bench.step", 0.0, 40 * ms),
             ("gen", "bench.submit", 50 * ms, 100 * ms)]
    mods = [("/device:TPU:0", "m", 0.0, 20 * ms)]
    gaps = dict(fake(monkeypatch, mods, spans)["idle_gaps"])
    # [20,100]: 20 ms under the loop's bench.step, 60 ms uncovered by the
    # loop, named by the span open at the gap's midpoint (60 ms)
    assert gaps == pytest.approx({"bench.step": 0.020,
                                  "bench.submit": 0.060})


def test_busy_is_averaged_over_devices(monkeypatch):
    ms = 1e6
    spans = [("main", "bench.window", 0.0, 100 * ms)]
    mods = [("/device:TPU:0", "m", 0.0, 50 * ms),
            ("/device:TPU:1", "m", 0.0, 30 * ms)]
    r = fake(monkeypatch, mods, spans)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(0.040)


def test_no_window_span_is_an_error(monkeypatch):
    with pytest.raises(ValueError):
        fake(monkeypatch, [], [("main", "bench.step", 0.0, 1.0)])
