"""The device's idle time named by the program's ``gopt.*`` spans
(``bench/span_reduce.py``) and the readers of the idle and sync metrics:
on hand-made events, on the recorded CPU trace that has no program spans,
and on a CPU trace of the served path (its numbers test the attribution
and the counts, and are no device numbers)."""
import json
import shutil
from pathlib import Path

import pytest

import run as bench
import span_reduce as S
import trace_reduce as T

BENCH = Path(__file__).resolve().parents[1]
TRACE = BENCH / "tests" / "data" / "cpu_window.xplane.pb"
MS = 1e6
READERS = ("syncs_per_wave", "idle_sync_ms", "idle_ops_ms",
           "idle_execute_ms", "idle_wave_ms")


def test_reduce_reads_as_before_without_program_spans():
    r = T.reduce(str(TRACE), device_plane="/host:CPU")
    assert r["busy_s"] == pytest.approx(0.01170178, rel=1e-12)
    assert r["device_ops"] == [["jit__lambda", pytest.approx(0.01170178,
                                                             rel=1e-12)]]
    assert [k for k, _ in r["idle_gaps"]] == ["bench.idle", "bench.step",
                                              "(no span)"]
    assert [v for _, v in r["idle_gaps"]] == pytest.approx(
        [0.062299989, 0.001808205, 0.000179067], rel=1e-9)
    sp = S.split(str(TRACE), device_plane="/host:CPU")
    assert sp["idle_by_span"] == {} and sp["waves"] == sp["syncs"] == 0
    assert sp["idle_gaps"] == r["idle_gaps"]
    assert sp["busy_s"] == r["busy_s"]
    assert sp["idle_no_span_s"] == pytest.approx(sp["idle_s"], rel=1e-12)


def fake(monkeypatch, modules, spans):
    monkeypatch.setattr(S, "read_events", lambda path, plane: (modules,
                                                                spans))
    return S.split("unused")


# a wave on the worker thread inside the serving loop's bench.step: three
# levels of program spans under it, the device busy twice
WAVE = [("main", "bench.window", 0.0, 100 * MS),
        ("main", "bench.step", 0.0, 100 * MS),
        ("worker", "gopt.wave", 10 * MS, 90 * MS),
        ("worker", "gopt.execute", 20 * MS, 80 * MS),
        ("worker", "gopt.op.EXPAND", 30 * MS, 60 * MS),
        ("worker", "gopt.sync.expand", 40 * MS, 50 * MS)]
BUSY = [("/device:TPU:0", "m", 50 * MS, 55 * MS),
        ("/device:TPU:0", "m", 85 * MS, 95 * MS)]


def test_idle_is_named_by_the_innermost_program_span(monkeypatch):
    sp = fake(monkeypatch, BUSY, WAVE)
    # idle [0,50] [55,85] [95,100]: [0,10] and [95,100] outside the wave
    assert sp["idle_by_span"] == pytest.approx({
        "gopt.wave": 0.015,           # [10,20] [80,85]
        "gopt.execute": 0.030,        # [20,30] [60,80]
        "gopt.op.EXPAND": 0.015,      # [30,40] [55,60]
        "gopt.sync.expand": 0.010})   # [40,50]
    assert sp["idle_no_span_s"] == pytest.approx(0.015)
    assert sp["busy_in_wave_s"] == pytest.approx(0.010)   # [50,55] [85,90]
    assert (sp["waves"], sp["syncs"], sp["ops"]) == (1, 1, 1)


def test_uncovered_idle_falls_back_to_the_benchmark_spans(monkeypatch):
    spans = WAVE + [("gen", "bench.submit", 92 * MS, 100 * MS)]
    gaps = dict(fake(monkeypatch, BUSY, spans)["idle_gaps"])
    # the loop's bench.step names what no program span covers, as
    # trace_reduce names it, and wins over the generator's bench.submit
    assert gaps["bench.step"] == pytest.approx(0.015)
    assert "bench.submit" not in gaps
    assert gaps["gopt.sync.expand"] == pytest.approx(0.010)


def test_only_the_windows_waves_are_counted(monkeypatch):
    # a wave before the window, with its operator and sync, and one that
    # straddles the window's end; a sync outside any wave
    spans = WAVE + [("worker", "gopt.wave", -30 * MS, -10 * MS),
                    ("worker", "gopt.op.SCAN", -25 * MS, -15 * MS),
                    ("worker", "gopt.sync.nonzero", -20 * MS, -18 * MS),
                    ("worker", "gopt.wave", 95 * MS, 120 * MS),
                    ("worker", "gopt.op.GROUP", 97 * MS, 110 * MS),
                    ("worker", "gopt.sync.group", 105 * MS, 108 * MS),
                    ("other", "gopt.sync.expand", 92 * MS, 93 * MS)]
    sp = fake(monkeypatch, BUSY, spans)
    assert (sp["waves"], sp["syncs"], sp["ops"]) == (2, 2, 2)


def test_the_latest_started_span_wins_across_threads(monkeypatch):
    spans = [("main", "bench.window", 0.0, 100 * MS),
             ("a", "gopt.wave", 0.0, 100 * MS),
             ("b", "gopt.execute", 30 * MS, 60 * MS),
             ("a", "gopt.op.SCAN", 40 * MS, 50 * MS)]
    sp = fake(monkeypatch, [], spans)
    assert sp["idle_by_span"] == pytest.approx({
        "gopt.wave": 0.070, "gopt.execute": 0.020, "gopt.op.SCAN": 0.010})


def test_the_partition_sums_to_the_idle_time(monkeypatch):
    spans = WAVE + [("worker", "gopt.wave", 91 * MS, 99 * MS),
                    ("worker", "gopt.d2h", 92 * MS, 96 * MS)]
    sp = fake(monkeypatch, BUSY, spans)
    idle = sp["window_s"] - sp["busy_s"]
    assert sp["idle_s"] == pytest.approx(idle)
    assert sum(sp["idle_by_span"].values()) + sp["idle_no_span_s"] == \
        pytest.approx(idle, rel=1e-12)
    assert sum(v for _, v in sp["idle_gaps"]) == pytest.approx(idle,
                                                               rel=1e-12)


@pytest.fixture
def readers(tmp_path):
    """The readers as ``bench/run.py`` loads them, from a checkout that
    holds one trace file."""
    shutil.copytree(BENCH / "metrics", tmp_path / "bench" / "metrics")
    xp = tmp_path / S.TRACE_DIR / "cell" / "plugins" / "profile" / "t"
    xp.mkdir(parents=True)
    (xp / "x.xplane.pb").write_bytes(b"")
    return {n: bench.load_reader(tmp_path, n) for n in READERS}


def ctx(window_s, waves):
    return {"trace": {"window_s": window_s},
            "waves": {"exec_s": [0.01] * waves}}


def test_readers_divide_by_the_window_waves(readers, monkeypatch):
    monkeypatch.setattr(S, "split", lambda path: {
        "window_s": 1.0, "waves": 4, "syncs": 10,
        "idle_by_span": {"gopt.sync.nonzero": 0.002, "gopt.d2h": 0.002,
                         "gopt.op.SCAN": 0.004, "gopt.tail": 0.004,
                         "gopt.execute": 0.006, "gopt.wave": 0.008}})
    got = {n: r(ctx(1.0, 4)) for n, r in readers.items()}
    assert got == pytest.approx({"syncs_per_wave": 2.5, "idle_sync_ms": 1.0,
                                 "idle_ops_ms": 2.0, "idle_execute_ms": 1.5,
                                 "idle_wave_ms": 2.0})


@pytest.mark.parametrize("split", [
    # a program without spans (the parent of this reduction)
    {"window_s": 1.0, "waves": 0, "syncs": 0, "idle_by_span": {}},
    # another run's trace: its window is not the one reduced
    {"window_s": 2.0, "waves": 4, "syncs": 8,
     "idle_by_span": {"gopt.wave": 0.1}}])
def test_readers_read_nothing_without_this_runs_spans(readers, monkeypatch,
                                                      split):
    monkeypatch.setattr(S, "split", lambda path: split)
    assert {n: r(ctx(1.0, 4)) for n, r in readers.items()} == \
        dict.fromkeys(READERS)


# ------------------------------------------------------ the served path
def _contains(outer, inner):
    return outer[2] <= inner[2] and inner[3] <= outer[3] \
        and outer[0] == inner[0]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A few IS-mix requests on a tiny store through ``GOpt.serve`` on the
    jax backend, traced: first one request per wave, then one wave of
    four distinct bindings."""
    import jax
    import datagen
    import traffic
    from repro.core.gopt import GOpt
    cfg = json.loads((BENCH / "configs" / "snb_sf0.1.json").read_text())
    cfg["sf"] = 0.003
    mix = json.loads((BENCH / "traffic" / "is_closed.json").read_text())
    store = datagen.build_program_store(datagen.generate(cfg, 2**31 + 5))
    gopt = GOpt(store, backend="jax")
    srv = gopt.serve(backend="jax")
    plans = [gopt.prepare(q["text"], backend="jax") for q in mix["queries"]]
    rng = traffic.rng_for(2**31 + 5, 0)
    singles = [(qi, traffic.draw_params(rng, q, store.v_count))
               for qi, q in enumerate(mix["queries"]) for _ in range(2)]
    wave = [traffic.draw_params(rng, mix["queries"][0], store.v_count)
            for _ in range(4)]
    for qi, p in singles:              # compile every shape first
        srv.submit(plans[qi], p)
    for p in wave:
        srv.submit(plans[0], p)
    srv.drain()
    k0, w0 = dict(srv.stats.kernels), srv.stats.waves
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        one = []
        for qi, p in singles:
            one.append(srv.submit(plans[qi], p))
            srv.drain()
        for p in wave:
            srv.submit(plans[0], p)
        srv.drain()
    jax.profiler.stop_trace()
    srv.close()
    path = T.find_xplane(str(out))
    _, spans = S.read_events(path, "/host:CPU")
    syncs = sum(n - k0.get(k, 0) for k, n in srv.stats.kernels.items()
                if k.startswith("sync:"))
    return {"spans": [s for s in spans if s[1].startswith("gopt.")],
            "split": S.split(path, "/host:CPU"), "syncs": syncs,
            "waves": srv.stats.waves - w0, "singles": one}


def test_served_spans_nest_wave_execute_phase_op_sync(served):
    spans = served["spans"]

    def outer(inner, pred):
        return [s for s in spans if pred(s[1]) and _contains(s, inner)]
    syncs = [s for s in spans if s[1].startswith("gopt.sync.")]
    assert syncs
    full = 0
    for s in syncs:
        phases = outer(s, lambda n: n in ("gopt.pattern", "gopt.tail"))
        assert len(phases) == 1, s
        execs = outer(phases[0], lambda n: n == "gopt.execute")
        assert execs and outer(execs[0], lambda n: n == "gopt.wave"), s
        full += any(_contains(phases[0], op) for op in
                    outer(s, lambda n: n.startswith("gopt.op.")))
    assert full == len(syncs)
    assert len({s[0] for s in spans}) == 1     # the wave worker's thread


def test_served_sync_spans_match_the_sync_counter(served):
    sp = served["split"]
    assert sp["syncs"] == served["syncs"] > 0
    assert sp["waves"] == served["waves"]


def test_served_op_spans_match_execstats(served):
    # one request per wave: the wave's operator spans are the operators
    # its request's ExecStats logged (PROFILE's op_rows/op_times)
    spans = served["spans"]
    waves = sorted((s for s in spans if s[1] == "gopt.wave"),
                   key=lambda s: s[2])
    ops_in = [sum(1 for s in spans
                  if s[1].startswith("gopt.op.") and _contains(w, s))
              for w in waves]
    assert sum(ops_in) == served["split"]["ops"]
    assert ops_in[:-1] == [len(r.stats.op_rows) for r in served["singles"]]
    assert [n for n, _ in served["singles"][0].stats.op_times] == \
        [n for n, _ in served["singles"][0].stats.op_rows]


def test_served_idle_partition(served):
    sp = served["split"]
    assert sum(sp["idle_by_span"].values()) + sp["idle_no_span_s"] == \
        pytest.approx(sp["idle_s"], rel=1e-9)
    assert set(sp["idle_by_span"]) <= {n for _, n, _, _ in served["spans"]}
