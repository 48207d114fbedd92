"""QueryServer continuous batching (DESIGN.md §9).

1. Wave formation: FIFO-fair per-plan coalescing with pow2 wave sizing, so
   recurring waves re-hit the backend's bucketed compile caches — counter-
   asserted: a warmed server's waves record zero compile events.
2. Serving is row-identical to sequential ``execute`` per request, on both
   backends, including mixed-plan traffic and overlap mode.
3. Admission control: bounded queue backpressure (``ServeOverload``),
   deadline drops at wave formation, host-side binding validation.
4. Wave-scoped instrumentation: both backend ledgers reset per wave — no
   bleed into a neighboring wave's PROFILE window, bounded growth.
5. Hotness pinning: a hot plan's fused-chain program survives chain-LRU
   pressure that evicts unpinned entries.
6. ``Engine.run_batch`` degraded paths record themselves in
   ``ExecStats.fallbacks`` and stay row-identical to the loop.
"""
import time
import types

import numpy as np
import pytest

from benchmarks import queries as Q
from repro.core.errors import ParamError
from repro.core.gopt import GOpt
from repro.core.physical_spec import get_spec
from repro.graphdb import jax_backend
from repro.graphdb.engine import Engine
from repro.graphdb.ldbc import generate_ldbc
from repro.graphdb.serve import (QueryServer, ServeOverload, ServeStats,
                                 _pow2_floor)

SIMPLE = ("MATCH (p:PERSON)-[:KNOWS]->(q:PERSON) "
          "WHERE p.id = $pid RETURN q.id AS friend")
CHAIN = ("MATCH (p:PERSON)-[:KNOWS]->(q:PERSON)-[:LIKES]->(m:POST) "
         "WHERE p.id = $pid RETURN q.id AS friend, m.id AS post")
THREE_HOP = ("MATCH (a:PERSON)-[:KNOWS*3]-(z:PERSON) "
             "WHERE a.id = $pid RETURN count(z) AS c")
STRLIT = ("MATCH (p:PERSON)-[:KNOWS]->(q:PERSON) "
          "WHERE p.id = $pid RETURN q.id AS friend, 'hot' AS tag")


@pytest.fixture(scope="module")
def serve_gopt():
    return GOpt(generate_ldbc(sf=0.05, seed=7))


def _table_eq(a, b, msg=""):
    assert a.nrows == b.nrows, f"{msg}: {a.nrows} != {b.nrows}"
    assert set(a.cols) == set(b.cols), msg
    for k in a.cols:
        np.testing.assert_array_equal(np.asarray(a.cols[k]),
                                      np.asarray(b.cols[k]),
                                      err_msg=f"{msg}/{k}")


# ------------------------------------------------------------ wave formation

def test_wave_sizes_follow_pow2_buckets(serve_gopt):
    """With a remainder queued, wave sizes round down to a power of two
    (6 -> 4); the draining wave takes whatever is left."""
    srv = serve_gopt.serve(backend="numpy", max_wave=6, overlap=False)
    for pid in range(13):
        srv.submit(SIMPLE, {"pid": pid})
    done = srv.drain()
    srv.close()
    assert len(done) == 13 and all(r.status == "done" for r in done)
    assert srv.stats.wave_sizes == [4, 4, 5]
    assert srv.stats.occupancy == [1.0, 1.0, 5 / 8]
    assert srv.stats.completed == 13


def test_wave_dedupes_identical_bindings(serve_gopt):
    """Identical bindings coalesced into one wave execute once; duplicate
    requests share the result row-identically."""
    srv = serve_gopt.serve(backend="numpy", max_wave=8, overlap=False)
    reqs = [srv.submit(SIMPLE, {"pid": p}) for p in (1, 2, 1, 2, 1, 2, 1, 1)]
    srv.drain()
    srv.close()
    assert srv.stats.deduped == 6
    ref = {p: serve_gopt.prepare(SIMPLE, backend="numpy").execute(
        {"pid": p})[0] for p in (1, 2)}
    for r in reqs:
        assert r.status == "done"
        _table_eq(r.table, ref[r.params["pid"]])
    assert reqs[0].table is reqs[2].table       # fanned out, not re-run


def test_pow2_floor():
    assert [_pow2_floor(n) for n in (1, 2, 3, 6, 8, 13)] == [1, 2, 2, 4, 8, 8]


# ------------------------------------------------------------------- parity

@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_serve_parity_mixed_plans(serve_gopt, backend):
    """Interleaved traffic over two plans, coalesced per plan under
    overlap, stays row-identical to sequential execution per request."""
    pq_a = serve_gopt.prepare(SIMPLE, backend=backend)
    pq_b = serve_gopt.prepare(CHAIN, backend=backend)
    ref = {("a", p): pq_a.execute({"pid": p})[0] for p in range(6)}
    ref.update({("b", p): pq_b.execute({"pid": p})[0] for p in range(6)})

    srv = serve_gopt.serve(backend=backend, max_wave=4, overlap=True)
    tagged = []
    for p in range(6):                       # interleaved arrivals
        tagged.append(("a", srv.submit(SIMPLE, {"pid": p})))
        tagged.append(("b", srv.submit(CHAIN, {"pid": p})))
    done = srv.drain()
    srv.close()
    assert len(done) == 12
    for tag, r in tagged:
        assert r.status == "done"
        _table_eq(r.table, ref[(tag, r.params["pid"])], f"{tag}/{r.params}")
    # each wave serves exactly one plan; both plans got waves
    assert len(srv.stats.per_plan) == 2
    assert sum(p["waves"] for p in srv.stats.per_plan.values()) \
        == srv.stats.waves


# --------------------------------------------------------- admission control

def test_backpressure_bounded_queue(serve_gopt):
    srv = serve_gopt.serve(backend="numpy", max_pending=3, overlap=False)
    for pid in range(3):
        srv.submit(SIMPLE, {"pid": pid})
    with pytest.raises(ServeOverload):
        srv.submit(SIMPLE, {"pid": 99})
    assert srv.stats.rejected == 1
    done = srv.drain()
    srv.close()
    assert len(done) == 3 and srv.stats.completed == 3


def test_deadline_drop_at_wave_formation(serve_gopt):
    srv = serve_gopt.serve(backend="numpy", overlap=False)
    live = [srv.submit(SIMPLE, {"pid": p}) for p in (1, 2)]
    past = time.perf_counter() - 1.0
    dead = [srv.submit(SIMPLE, {"pid": p}, deadline_s=past) for p in (3, 4)]
    srv.drain()
    srv.close()
    assert all(r.status == "done" for r in live)
    assert all(r.status == "dropped" and r.table is None for r in dead)
    assert srv.stats.dropped == 2 and srv.stats.completed == 2


def test_admission_validates_bindings(serve_gopt):
    srv = serve_gopt.serve(backend="numpy")
    with pytest.raises(ParamError):                  # unknown name
        srv.submit(SIMPLE, {"nope": 1})
    with pytest.raises(ParamError):                  # unbound $pid
        srv.submit(SIMPLE, {})
    assert srv.pending == 0 and srv.stats.submitted == 0
    srv.close()


# ------------------------------------------------------- wave-scoped ledgers

def test_ledgers_scoped_per_wave(serve_gopt):
    """Both instrumentation ledgers reset at wave start: a warmed wave's
    ledger holds only its own events (no bleed, no unbounded growth)."""
    srv = serve_gopt.serve(backend="jax", max_wave=4, overlap=False)
    ops = get_spec("jax").operators(serve_gopt.store)
    lens = []
    for pid in range(12):
        srv.submit(CHAIN, {"pid": pid})
    while srv.pending:
        srv.step()
        lens.append((ops.kernel_stats.mark(), ops.transfer_stats.mark()))
    srv.close()
    assert len(lens) == 3
    # warmed waves of equal size leave equal (small) ledgers behind —
    # cumulative ledgers would grow by ~wave-size every step
    assert 0 < lens[2][0] <= lens[1][0]
    assert 0 < lens[2][1] <= lens[1][1]


# ------------------------------------------------------------ hotness pinning

def test_hot_chain_survives_lru_pressure(serve_gopt):
    """Serving pins the hot plan's fused-chain handle; chain-LRU pressure
    evicts unpinned entries around it.  Unpinning makes the same entry the
    eviction victim — the protection is the pin, not luck."""
    srv = serve_gopt.serve(backend="jax", max_wave=8, overlap=False,
                           hot_plans=1)
    for pid in range(8):
        srv.submit(CHAIN, {"pid": pid})
    srv.drain()
    srv.close()
    ops = get_spec("jax").operators(serve_gopt.store)
    pinned = [k for k, v in ops._chains.items()
              if getattr(v, "pinned", False)]
    assert pinned, "serving a single hot plan must pin its chain"
    fakes = []
    try:
        i = 0
        while len(ops._chains) < jax_backend._CHAIN_SHAPES:
            k = ("fake", i)
            ops._chains[k] = types.SimpleNamespace(pinned=False)
            fakes.append(k)
            i += 1
        # inserting a new real chain at capacity evicts an unpinned entry
        serve_gopt.prepare(THREE_HOP, backend="jax").execute({"pid": 5})
        assert all(k in ops._chains for k in pinned)
        assert any(k not in ops._chains for k in fakes)
        # release the pin: the same entry is now fair game
        for k in pinned:
            ops._chains[k].pinned = False
        while len(ops._chains) < jax_backend._CHAIN_SHAPES:
            k = ("fake", i)
            ops._chains[k] = types.SimpleNamespace(pinned=False)
            fakes.append(k)
            i += 1
        serve_gopt.prepare(Q.QIC["ic12"], backend="jax").execute({"pid": 5})
        assert any(k not in ops._chains for k in pinned)
    finally:
        for k in fakes:
            ops._chains.pop(k, None)


# --------------------------------------------------- warmed compile flatness

def test_warm_server_compiles_stay_flat(serve_gopt):
    """Acceptance: pow2 wave sizing + bucketed kernels hold a warmed
    server's per-wave compile count at zero."""
    srv = serve_gopt.serve(backend="jax", max_wave=8, overlap=False)
    for pid in range(32):
        srv.submit(CHAIN, {"pid": pid})
    done = srv.drain()
    chain = srv.stats.kernels.get("compile:fused_chain", 0)
    for pid in range(32, 40):
        srv.submit(CHAIN, {"pid": pid})
    done += srv.drain()
    srv.close()
    assert len(done) == 40 and sum(srv.stats.wave_sizes) == 40
    assert srv.stats.wave_compiles[-1] == 0, srv.stats.wave_compiles
    assert srv.stats.kernels.get("compile:fused_chain", 0) == chain


# ----------------------------------------------------------- EXPLAIN surface

def test_explain_carries_serve_section(serve_gopt):
    srv = serve_gopt.serve(backend="numpy", max_wave=4, overlap=False)
    for pid in range(8):
        srv.submit(SIMPLE, {"pid": pid})
    srv.drain()
    report = srv.explain(SIMPLE)
    srv.close()
    assert report.serve and report.serve["requests"] == 8
    txt = report.render()
    assert "-- serve --" in txt and "mean_wave_size" in txt


def test_serve_stats_render_smoke():
    s = ServeStats()
    assert "0/0 completed" in s.render()


# ------------------------------------------- run_batch fallback bookkeeping

def test_stacked_tail_error_falls_back_to_loop(serve_gopt, monkeypatch):
    """A RuntimeError out of the segmented tail stack degrades to the
    per-binding loop — row-identical — and records itself."""
    bindings = [{"pid": p} for p in (1, 3, 5)]
    pq = serve_gopt.prepare(Q.QIC["ic1"], backend="jax")
    loop = pq.execute_many(bindings, batch=False)

    def boom(self, *a, **k):
        raise RuntimeError("segment stack exploded")

    monkeypatch.setattr(Engine, "_run_tails_stacked", boom)
    batched = pq.execute_many(bindings, batch=True)
    for (lt, _), (bt, bst) in zip(loop, batched):
        _table_eq(lt, bt)
        assert bst.fallbacks.get("stacked_tail_error") == 1, bst.fallbacks


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_all_empty_batch_needs_no_fallback(serve_gopt, backend):
    """A batch in which no binding matches anything stays on the stacked
    path (each binding gets the loop's empty-input semantics there)."""
    bindings = [{"pid": p} for p in (-1, -2, -3)]
    pq = serve_gopt.prepare(Q.QIC["ic1"], backend=backend)
    loop = pq.execute_many(bindings, batch=False)
    batched = pq.execute_many(bindings, batch=True)
    for (lt, _), (bt, bst) in zip(loop, batched):
        _table_eq(lt, bt)
        assert not bst.fallbacks, bst.fallbacks


def test_device_error_in_stacked_tail_raises(serve_gopt, monkeypatch):
    """A JAX runtime error (a ``RuntimeError`` subclass) out of the
    segmented tail is a failing device, not a tail limit: it surfaces
    instead of falling back to the per-binding loop."""
    import jax
    pq = serve_gopt.prepare(Q.QIC["ic1"], backend="jax")

    def boom(self, *a, **k):
        raise jax.errors.JaxRuntimeError("INTERNAL: injected device fault")

    monkeypatch.setattr(Engine, "_run_tails_stacked", boom)
    with pytest.raises(jax.errors.JaxRuntimeError, match="injected"):
        pq.execute_many([{"pid": p} for p in (1, 3, 5)], batch=True)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_unstackable_tail_records_fallback(serve_gopt, backend):
    """A tail the segment pass cannot carry (string-literal output) runs
    the loop and says so in ``ExecStats.fallbacks``."""
    bindings = [{"pid": p} for p in (1, 2, 3)]
    pq = serve_gopt.prepare(STRLIT, backend=backend)
    loop = pq.execute_many(bindings, batch=False)
    batched = pq.execute_many(bindings, batch=True)
    for (lt, _), (bt, bst) in zip(loop, batched):
        _table_eq(lt, bt)
        assert bst.fallbacks.get("tail_unstackable") == 1, bst.fallbacks
    assert all(not lst.fallbacks for _, lst in loop)


# -------------------------------------------- bucketed tail-kernel plateaus

def test_tail_kernel_compiles_plateau(serve_gopt):
    """Jittered input sizes land in pow2 capacity buckets: compile events
    plateau at the handful of distinct buckets while call counts grow."""
    ops = get_spec("jax").make_operators(serve_gopt.store)
    ks = ops.kernel_stats
    m = ks.mark()
    rng = np.random.default_rng(0)
    for n in rng.integers(90, 126, 24):          # all inside the 128 bucket
        n = int(n)
        keys = ops.asarray(rng.integers(0, 17, n))
        vals = ops.asarray(rng.integers(0, 100, n))
        ops.combine_keys([keys, vals])
        ops.group_reduce(keys, {"s": ("SUM", vals)})
        ops.join(keys, ops.asarray(rng.integers(0, 17, n)))
    assert ks.count("compile", "lex_ranks", since=m) <= 2
    assert ks.count("compile", "group", since=m) <= 2
    assert ks.count("compile", "group_agg", since=m) <= 2
    assert ks.count("compile", "join", since=m) <= 2
    # the same shapes re-presented add zero compile events
    m2 = ks.mark()
    keys = ops.asarray(rng.integers(0, 17, 100))
    ops.combine_keys([keys, keys])
    ops.group_reduce(keys, {"s": ("SUM", keys)})
    ops.join(keys, keys)
    assert sum(1 for k, _, _ in ks.events[m2:] if k == "compile") == 0


# ------------------------------------------------------- mixed-backend serving

def test_mixed_backend_servers_isolated_ledgers(serve_gopt):
    """Two servers over DIFFERENT physical backends in one process: traffic
    interleaves arbitrarily, yet each stays row-identical to sequential
    execution and each plan's ledger window holds only its own backend's
    events — a numpy wave never bleeds kernel events into the jax ledger."""
    ref = {p: serve_gopt.prepare(SIMPLE, backend="numpy").execute(
        {"pid": p})[0] for p in range(8)}

    srv_np = serve_gopt.serve(backend="numpy", max_wave=4, overlap=False)
    srv_jx = serve_gopt.serve(backend="jax", max_wave=4, overlap=False)
    jax_ops = get_spec("jax").operators(serve_gopt.store)
    np_results, jx_results = [], []
    for p in range(8):                        # interleaved across servers
        np_results.append(srv_np.submit(SIMPLE, {"pid": p}))
        jx_results.append(srv_jx.submit(SIMPLE, {"pid": p}))
    while srv_jx.pending:                     # jax server runs its waves
        srv_jx.step()
    m = jax_ops.kernel_stats.mark()
    while srv_np.pending:                     # numpy waves: no jax events
        srv_np.step()
    assert jax_ops.kernel_stats.mark() == m
    srv_np.close()
    srv_jx.close()

    for r in np_results + jx_results:
        assert r.status == "done"
        _table_eq(r.table, ref[r.params["pid"]], f"pid={r.params['pid']}")
    # per-plan accounting stays per-server: each saw exactly its own waves
    assert sum(p["waves"] for p in srv_np.stats.per_plan.values()) \
        == srv_np.stats.waves > 0
    assert sum(p["waves"] for p in srv_jx.stats.per_plan.values()) \
        == srv_jx.stats.waves > 0


# --------------------------------------------------------- fault tolerance

def test_submit_storm_every_request_terminal(serve_gopt):
    """Concurrent submitters racing the serving loop: every admitted
    request ends in exactly one terminal status and the conservation
    equation holds (submitted = completed + failed + dropped + cancelled,
    with overload rejections accounted separately)."""
    import threading

    srv = serve_gopt.serve(backend="numpy", max_wave=8, max_pending=64,
                           overlap=True)
    accepted, rejected = [], []
    lock = threading.Lock()

    def storm(seed):
        rng = np.random.default_rng(seed)
        for _ in range(40):
            q = (SIMPLE, STRLIT)[int(rng.integers(0, 2))]
            try:
                r = srv.submit(q, {"pid": int(rng.integers(0, 12))})
                with lock:
                    accepted.append(r)
            except ServeOverload:
                with lock:
                    rejected.append(1)
            if rng.random() < 0.1:
                time.sleep(0.001)

    threads = [threading.Thread(target=storm, args=(s,)) for s in range(4)]
    for t in threads:
        t.start()
    while any(t.is_alive() for t in threads) or srv.pending:
        srv.step()
    for t in threads:
        t.join()
    srv.drain()
    srv.close()

    terminal = {"done", "failed", "dropped", "cancelled"}
    assert len(accepted) + len(rejected) == 160
    assert all(r.status in terminal for r in accepted)
    s = srv.stats.summary()
    assert s["submitted"] == len(accepted)
    assert s["rejected"] == len(rejected)
    assert s["submitted"] == (s["completed"] + s["failed"] + s["dropped"]
                              + s["cancelled"])
    # this storm has no faults and no deadlines: everything completed
    assert s["failed"] == s["dropped"] == s["cancelled"] == 0
    ref = {p: serve_gopt.prepare(SIMPLE, backend="numpy").execute(
        {"pid": p})[0] for p in range(12)}
    for r in accepted:
        if r.prepared.source == SIMPLE:
            _table_eq(r.table, ref[r.params["pid"]], "storm parity")


def test_close_cancels_queued_requests(serve_gopt):
    srv = serve_gopt.serve(backend="numpy", overlap=False)
    done = srv.submit(SIMPLE, {"pid": 1})
    srv.drain()
    queued = [srv.submit(SIMPLE, {"pid": p}) for p in (2, 3)]
    srv.close()
    assert done.status == "done"
    assert all(r.status == "cancelled" for r in queued)
    assert all(r.finish_s > 0 for r in queued)
    assert srv.stats.cancelled == 2
    assert srv.pending == 0
    s = srv.stats.summary()
    assert s["submitted"] == (s["completed"] + s["failed"] + s["dropped"]
                              + s["cancelled"])


def test_compact_counts_unwarmable_plans():
    """The warm loop narrowly skips plans whose remembered sample binding
    no longer binds (ParamError) — counted, not silently swallowed — and
    anything else propagates instead of hiding behind the old bare
    ``except Exception: continue``."""
    from repro.graphdb.delta import MutableGraphStore
    gopt = GOpt(MutableGraphStore(generate_ldbc(sf=0.05, seed=7)))
    gopt.store.insert_vertex("PERSON", {"id": 800_000})   # give compact work
    srv = gopt.serve(backend="numpy", overlap=False, hot_plans=2)
    for p in range(4):
        srv.submit(SIMPLE, {"pid": p})
    srv.drain()
    key = next(iter(srv._plans))
    srv._samples[key] = None                     # sample no longer binds
    ev = srv.compact()
    assert ev["warm_skips"] == 1
    assert ev["repinned_plans"] == 0
    # a non-ParamError failure in the warm loop must escape
    for p in range(4):
        srv.submit(SIMPLE, {"pid": p})
    srv.drain()
    srv._samples[key] = {"pid": 0}
    srv.exec_kw = dict(srv.exec_kw, not_an_exec_kwarg=1)
    with pytest.raises(TypeError):
        srv.compact()
    srv.close()
