"""Program spans and the sync counter: host backends open no-op spans and
never import jax; the jax backend's spans are profiler annotations; every
control-plane sync is one ``sync:<label>`` event."""
import contextlib
import os
import subprocess
import sys
from pathlib import Path

from repro.core.gopt import GOpt
from repro.core.physical_spec import get_spec

Q = "MATCH (p:PERSON)-[:KNOWS]->(f:PERSON) WHERE p.id = $pid RETURN f.id"


def test_numpy_spans_are_no_ops(tiny_store):
    ops = get_spec("numpy").operators(tiny_store)
    assert isinstance(ops.span("gopt.wave", wave=0, n=1, rids="0"),
                      contextlib.nullcontext)
    with ops.phase("tail"):
        assert ops.transfer_stats.phase == "tail"
    assert ops.transfer_stats.phase == ""


def test_numpy_serving_never_imports_jax():
    src = Path(__file__).resolve().parent.parent / "src"
    code = (
        "import sys\n"
        "from repro.graphdb.ldbc import generate_motivating\n"
        "from repro.core.gopt import GOpt\n"
        "g = GOpt(generate_motivating(n_person=30, n_product=10,"
        " n_place=5))\n"
        "srv = g.serve(backend='numpy')\n"
        f"r = srv.submit({Q!r}, {{'pid': 1}})\n"
        "srv.drain(); srv.close()\n"
        "assert r.status == 'done', r.status\n"
        "assert 'jax' not in sys.modules\n")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["PYTHONPATH"] = str(src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr


def test_jax_spans_are_profiler_annotations(tiny_store):
    import jax
    ops = get_spec("jax").operators(tiny_store)
    assert isinstance(ops.span("gopt.op.SCAN"),
                      jax.profiler.TraceAnnotation)


def test_every_sync_is_one_counted_round_trip(tiny_store, monkeypatch):
    import jax
    ops = get_spec("jax").operators(tiny_store)
    fetched = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: fetched.append(x) or real(x))
    g = GOpt(tiny_store, backend="jax")
    _, stats = g.run(Q, {"pid": 3})
    syncs = {k: n for k, n in stats.kernels.items() if k.startswith("sync:")}
    assert syncs and sum(syncs.values()) == len(fetched)
    assert ops.kernel_stats.count("sync") >= len(fetched)



IS4_SHAPED = "MATCH (n:PERSON) WHERE n.id = $pid RETURN n.id"


def test_batched_refilter_is_one_sync(tiny_store, monkeypatch):
    """A 4-binding wave re-filters in one round trip: one ``nonzero`` for
    the union scan filter and one ``refilter``, under one BATCH_BIND."""
    ops = get_spec("jax").operators(tiny_store)
    names = []
    real = ops.span
    monkeypatch.setattr(ops, "span",
                        lambda name, **a: names.append(name) or real(name,
                                                                     **a))
    pq = GOpt(tiny_store, backend="jax").prepare(IS4_SHAPED)
    out = pq.execute_many([{"pid": p} for p in (3, 7, 11, 19)])
    assert [t.nrows for t, _ in out] == [1, 1, 1, 1]
    kernels = out[0][1].kernels
    assert kernels.get("sync:refilter") == 1
    assert kernels.get("sync:nonzero") == 1
    assert names.count("gopt.op.BATCH_BIND") == 1
    assert names.count("gopt.sync.refilter") == 1
    _, stats = pq.execute({"pid": 3})
    assert "sync:refilter" not in stats.kernels


def _bind_rows(st):
    """PROFILE's rows of one batched binding, checked for shape: the same
    labels in ``op_times`` as in ``op_rows``, and exactly one BATCH_BIND."""
    names = [n for n, _ in st.op_rows]
    assert [n for n, _ in st.op_times] == names
    assert all(t >= 0 for _, t in st.op_times)
    assert names.count("BATCH_BIND") == 1
    return names, dict(st.op_rows)["BATCH_BIND"]


def test_batched_profile_logs_one_bind_per_binding(gopt_small):
    from benchmarks import queries as Q
    pq = gopt_small.prepare(Q.QIC["ic1"], backend="numpy")
    none = 10**9
    for bindings in ([{"pid": 5}, {"pid": none}, {"pid": 3}],   # stacked
                     [{"pid": none}, {"pid": none + 1}],        # all empty
                     [{"pid": 3}]):                             # loop tail
        # one binding takes the per-binding loop tail
        out = gopt_small.execute_batch(pq.opt, bindings, backend="numpy")
        heads = set()
        for (tbl, st), b in zip(out, bindings):
            names, bind_rows = _bind_rows(st)
            # the shared pattern phase comes first, then the binding's own
            # re-filter, then the relational tail
            heads.add(tuple(names[:names.index("BATCH_BIND")]))
            assert names[0].startswith("SCAN(")
            if b["pid"] >= none:
                assert bind_rows == 0 and tbl.nrows == 0
        assert len(heads) == 1
