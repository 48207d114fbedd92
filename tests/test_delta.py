"""Delta store + MVCC-lite snapshots (DESIGN.md §11).

Covers: overlay read parity against a frozen deep-copy oracle on all three
backends, snapshot isolation under concurrent-style mutation, the zero
mid-plan-d2h residency contract with a non-empty overlay, compaction
round-trips against a from-scratch ``build_store`` oracle, stats-epoch
re-costing, chain decline/recovery, pow2 delta-capacity plateaus, the
re-optimize-on-binding-skew satellite, and the QueryServer update stream.
"""
import copy

import numpy as np
import pytest

from benchmarks import queries as Q
from repro.core.gopt import GOpt
from repro.core.physical_spec import TransferStats
from repro.graphdb.delta import (DeltaAdj, MutableGraphStore, Snapshot,
                                 StaleSnapshotError, _build_adj)
from repro.graphdb.ldbc import generate_motivating
from repro.graphdb.storage import build_store
from tests._hypothesis_compat import given, settings, st

QK = """MATCH (a:PERSON)-[:knows]->(b:PERSON)
RETURN a.id AS aid, b.id AS bid ORDER BY aid, bid"""
Q2HOP = """MATCH (a:PERSON)-[:knows]->(b:PERSON)-[:knows]->(c:PERSON)
RETURN a.id AS aid, c.id AS cid, count(b) AS n ORDER BY aid, cid"""
QPROPS = """MATCH (a:PERSON)-[:purchases]->(p:PRODUCT)
RETURN a.id AS aid, p.id AS pid ORDER BY aid, pid"""


def _rows(tbl):
    ks = sorted(tbl.cols)
    if tbl.nrows == 0:
        return []
    return sorted(zip(*[np.asarray(tbl.cols[k]).tolist() for k in ks]))


def _run(store, query, backend, params=None):
    tbl, stats = GOpt(store, backend=backend).run(query, params)
    return _rows(tbl), stats


def _mutable(seed=0):
    base = generate_motivating(n_person=50, n_product=20, n_place=8)
    return base, MutableGraphStore(base)


def _knows(base):
    return next(t for t in base.out_csr if t.label == "KNOWS")


def _apply_mix(ms, base, n=6):
    """A deterministic insert/delete mix touching vertices and edges."""
    kt = _knows(base)
    off = base.v_offset["PERSON"]
    new = []
    for i in range(n):
        gid = ms.insert_vertex("PERSON", {"id": 9000 + i})
        new.append(gid)
        ms.insert_edge(kt, off + i, gid)
    for i in range(1, n):
        ms.insert_edge(kt, new[i - 1], new[i])
    csr = base.out_csr[kt]
    row = int(np.argmax(np.diff(csr.indptr)))
    ms.delete_edge(kt, off + row, int(csr.indices[csr.indptr[row]]))
    ms.delete_vertex(new[-1])
    return new


# ------------------------------------------------------- overlay read parity
@pytest.mark.parametrize("backend", ["numpy", "jax", "sharded"])
def test_overlay_parity_vs_frozen_oracle(backend):
    """Acceptance: with live overlay (inserts + tombstones), every backend
    answers row-identically to a frozen deep-copy oracle of the same
    mutable store."""
    base, ms = _mutable()
    _apply_mix(ms, base)
    frozen = copy.deepcopy(ms)
    for query in (QK, Q2HOP, QPROPS):
        got, _ = _run(ms, query, backend)
        ref, _ = _run(frozen, query, "numpy")
        assert got == ref, query


@pytest.mark.parametrize("backend", ["numpy", "jax", "sharded"])
def test_snapshot_isolation_under_writes(backend):
    """A query pinned at snapshot S answers as-of S while a writer keeps
    landing inserts AND deletes: the result equals a frozen deep copy
    taken at S, on every backend."""
    base, ms = _mutable()
    kt = _knows(base)
    csr = base.out_csr[kt]
    off = base.v_offset["PERSON"]
    gopt = GOpt(ms, backend=backend)
    snaps = []
    for i in range(4):
        snaps.append((gopt.snapshot(), copy.deepcopy(ms)))
        gid = ms.insert_vertex("PERSON", {"id": 8800 + i})
        ms.insert_edge(kt, off + i, gid)
        row = int(np.argsort(np.diff(csr.indptr))[-(i + 1)])
        if csr.indptr[row] < csr.indptr[row + 1]:
            ms.delete_edge(kt, off + row, int(csr.indices[csr.indptr[row]]))
        if i == 2:
            ms.delete_vertex(gid)
    snaps.append((gopt.snapshot(), copy.deepcopy(ms)))
    for snap, frozen in snaps:
        tbl, _ = gopt.run(QK, snapshot=snap)
        ref, _ = _run(frozen, QK, "numpy")
        assert _rows(tbl) == ref


def test_chain_declines_on_delta_and_recovers_after_compaction():
    """Fused chains decline (``chain_delta`` fallback) only when the
    snapshot can change a hop: ext-only overlays keep the chain exact,
    touching a chain triple declines it with row parity preserved, and
    compaction restores the fused path."""
    base, ms = _mutable()
    kt = _knows(base)
    # unit-level affects_chain semantics
    ms.insert_vertex("PERSON", {"id": 9100})
    s = ms.snapshot()
    assert not s.affects_chain([kt])           # ext-only: chains stay exact
    gopt = GOpt(ms, backend="jax")
    o = gopt.optimize(Q2HOP, backend="jax", cbo=False)   # chain-shaped plan
    _, stats = gopt.execute(o, backend="jax")
    assert "chain_delta" not in (stats.fallbacks or {})
    # touch the chain's own triple -> decline + parity
    off = base.v_offset["PERSON"]
    ms.insert_edge(kt, off, off + 7)
    assert ms.snapshot().affects_chain([kt])
    got, stats2 = gopt.execute(o, backend="jax")
    assert (stats2.fallbacks or {}).get("chain_delta", 0) >= 1
    ref, _ = _run(copy.deepcopy(ms), Q2HOP, "numpy")
    assert _rows(got) == ref
    # a dead vertex affects every chain, touched or not
    ms.delete_vertex(ms.insert_vertex("PERSON"))
    pt = next(t for t in base.out_csr if t.label == "PURCHASES")
    assert ms.snapshot().affects_chain([pt])
    # compaction folds the overlay into the base: fused path is back
    gopt.compact()
    o3 = gopt.optimize(Q2HOP, backend="jax", cbo=False)
    got3, stats3 = gopt.execute(o3, backend="jax")
    assert "chain_delta" not in (stats3.fallbacks or {})
    assert _rows(got3) == ref


def test_mid_plan_d2h_zero_with_overlay():
    """Residency contract: a non-empty delta overlay stays device-resident —
    zero mid-plan device->host transfers on the jax backend."""
    base, ms = _mutable()
    _apply_mix(ms, base)
    gopt = GOpt(ms, backend="jax")
    tbl, stats = gopt.run(Q2HOP)
    assert tbl.nrows > 0
    assert stats.transfers is not None
    assert TransferStats.mid_plan_d2h(stats.transfers) == 0, stats.transfers


def test_overlay_props_roundtrip():
    """Properties of overlay vertices/edges gather correctly on both the
    host and device paths."""
    base, ms = _mutable()
    kt = _knows(base)
    g1 = ms.insert_vertex("PERSON", {"id": 9200, "age": 33})
    g2 = ms.insert_vertex("PERSON", {"id": 9201})
    ms.insert_edge(kt, g1, g2, {"weight": 7})
    ids = np.array([g1, g2, base.v_offset["PERSON"]], dtype=np.int64)
    host = ms.vertex_prop(ids, "id")
    assert host[0] == 9200 and host[1] == 9201
    age = ms.vertex_prop(ids, "age")
    assert age[0] == 33 and age[1] == np.iinfo(np.int64).min
    for backend in ("numpy", "jax"):
        got, _ = _run(ms, QK, backend)
        assert (9200, 9201) in got


# --------------------------------------------------------------- compaction
def test_compaction_matches_from_scratch_build(tiny_store):
    """Compacted store is ARRAY-identical to a from-scratch ``build_store``
    over the post-mutation graph (canonical renumbering: surviving base
    locals in order, then alive extension vertices in insertion order)."""
    base = tiny_store
    ms = MutableGraphStore(base)
    kt = _knows(base)
    off = base.v_offset["PERSON"]
    new = [ms.insert_vertex("PERSON", {"id": 9500 + i}) for i in range(3)]
    ms.insert_edge(kt, off + 2, new[0])
    ms.insert_edge(kt, new[0], new[1])
    csr = base.out_csr[kt]
    row = int(np.argmax(np.diff(csr.indptr)))
    ms.delete_edge(kt, off + row, int(csr.indices[csr.indptr[row]]))
    ms.delete_vertex(new[2])

    oracle = _scratch_oracle(base, ms)
    ms.compact()
    _assert_stores_identical(ms.base, oracle)


def _scratch_oracle(base, ms):
    """Independent reconstruction: extract base edges/props, apply the
    overlay in canonical-renumbering order, build_store from scratch."""
    bv = base.n_vertices
    old2new = np.full(ms.id_space, -1, dtype=np.int64)
    counts = {}
    ext_by_type = {}
    for s, t in enumerate(ms._ext_type):
        if ms._ext_alive[s]:
            ext_by_type.setdefault(t, []).append(s)
    vprops = {}
    for t in base.schema.vertex_types:
        lo, hi = base.type_range(t)
        keep = [g for g in range(lo, hi) if g not in ms._dead_base]
        exts = ext_by_type.get(t, [])
        for j, g in enumerate(keep + [bv + s for s in exts]):
            old2new[g] = j
        counts[t] = len(keep) + len(exts)
        props = set(base.v_props.get(t, {}))
        props |= {p for p, slots in ms._ext_props.items()
                  if any(s in slots for s in exts)}
        cols = {}
        for p in props:
            col = np.full(counts[t], np.iinfo(np.int64).min, dtype=np.int64)
            bcol = base.v_props.get(t, {}).get(p)
            if bcol is not None:
                col[:len(keep)] = bcol[np.asarray(keep, np.int64) - lo]
            for j, s in enumerate(exts):
                if s in ms._ext_props.get(p, {}):
                    col[len(keep) + j] = ms._ext_props[p][s]
            cols[p] = col
        if cols:
            vprops[t] = cols
    edges = {}
    eprops = {}
    for t, csr in base.out_csr.items():
        lo, _ = base.type_range(t.src)
        deg = np.diff(csr.indptr)
        gsrc = np.repeat(np.arange(deg.shape[0], dtype=np.int64) + lo, deg)
        gdst = csr.indices
        epos = np.arange(gdst.shape[0], dtype=np.int64)
        dset = ms._dels.get(t) or set()
        keep = np.array([old2new[s] >= 0 and old2new[d] >= 0
                         and (int(s), int(d)) not in dset
                         for s, d in zip(gsrc, gdst)], dtype=bool)
        gsrc, gdst, epos = gsrc[keep], gdst[keep], epos[keep]
        ins = [(k, v) for k, v in (ms._ins.get(t) or {}).items()
               if old2new[k[0]] >= 0 and old2new[k[1]] >= 0]
        all_src = np.concatenate(
            [old2new[gsrc], old2new[[k[0] for k, _ in ins]]]) \
            if ins else old2new[gsrc]
        all_dst = np.concatenate(
            [old2new[gdst], old2new[[k[1] for k, _ in ins]]]) \
            if ins else old2new[gdst]
        edges[t] = (all_src.astype(np.int64), all_dst.astype(np.int64))
        props = set(base.e_props.get(t, {}))
        props |= {p for p, slots in ms._eprops_over.items()
                  if any(v in slots for _, v in ins)}
        cols = {}
        for p in props:
            col = np.full(all_src.shape[0], np.iinfo(np.int64).min,
                          dtype=np.int64)
            bcol = base.e_props.get(t, {}).get(p)
            if bcol is not None:
                col[:gsrc.shape[0]] = bcol[epos]
            for j, (_, slot) in enumerate(ins):
                if slot in ms._eprops_over.get(p, {}):
                    col[gsrc.shape[0] + j] = ms._eprops_over[p][slot]
            cols[p] = col
        if cols:
            eprops[t] = cols
    return build_store(base.schema, counts, edges, v_props=vprops,
                       e_props=eprops, str_vocab=base.str_vocab)


def _assert_stores_identical(a, b):
    assert a.v_count == b.v_count
    assert set(a.out_csr) == set(b.out_csr)
    for t in a.out_csr:
        for attr in ("out_csr", "in_csr"):
            ca, cb = getattr(a, attr)[t], getattr(b, attr)[t]
            np.testing.assert_array_equal(ca.indptr, cb.indptr, err_msg=str(t))
            np.testing.assert_array_equal(ca.indices, cb.indices,
                                          err_msg=str(t))
            if ca.pos is not None or cb.pos is not None:
                np.testing.assert_array_equal(ca.pos, cb.pos, err_msg=str(t))
    assert set(a.v_props) == set(b.v_props)
    for t in a.v_props:
        assert set(a.v_props[t]) == set(b.v_props[t])
        for p in a.v_props[t]:
            np.testing.assert_array_equal(a.v_props[t][p], b.v_props[t][p])
    assert set(a.e_props) == set(b.e_props)
    for t in a.e_props:
        assert set(a.e_props[t]) == set(b.e_props[t])
        for p in a.e_props[t]:
            np.testing.assert_array_equal(a.e_props[t][p], b.e_props[t][p])


def test_compaction_random_sequences_row_parity():
    """Seeded random insert/delete sequences: the compacted store stays
    row-identical (value-level) to the live overlay answer just before
    compaction, and array-identical to the from-scratch oracle."""
    rng = np.random.default_rng(7)
    base, ms = _mutable()
    kt = _knows(base)
    off, n_p = base.v_offset["PERSON"], base.v_count["PERSON"]
    live = list(range(off, off + n_p))
    for step in range(60):
        op = rng.integers(0, 4)
        if op == 0:
            live.append(ms.insert_vertex("PERSON",
                                         {"id": 10_000 + step}))
        elif op == 1 and len(live) > 2:
            a, b = rng.choice(len(live), size=2, replace=False)
            ms.insert_edge(kt, live[a], live[b])
        elif op == 2 and len(live) > 2:
            a, b = rng.choice(len(live), size=2, replace=False)
            ms.delete_edge(kt, live[a], live[b])
        elif op == 3 and len(live) > n_p // 2:
            ms.delete_vertex(live.pop(int(rng.integers(0, len(live)))))
    pre, _ = _run(ms, QK, "numpy")
    oracle = _scratch_oracle(base, ms)
    ms.compact()
    _assert_stores_identical(ms.base, oracle)
    post, _ = _run(ms, QK, "numpy")
    assert post == pre


def test_post_compaction_appendix_a_row_identical(small_ldbc):
    """Acceptance: after mutating an LDBC store and compacting, every
    Appendix-A query answers row-identically to its pre-compaction
    (live-overlay) answer."""
    ms = MutableGraphStore(small_ldbc)
    kt = next(t for t in small_ldbc.out_csr if t.label == "KNOWS")
    off = small_ldbc.v_offset["PERSON"]
    new = [ms.insert_vertex("PERSON", {"id": 90_000 + i}) for i in range(4)]
    for i, gid in enumerate(new):
        ms.insert_edge(kt, off + i, gid)
    ms.insert_edge(kt, new[0], new[1])
    csr = small_ldbc.out_csr[kt]
    row = int(np.argmax(np.diff(csr.indptr)))
    ms.delete_edge(kt, off + row, int(csr.indices[csr.indptr[row]]))
    ms.delete_vertex(new[3])

    cases = [(n, t, None) for n, t in list(Q.QT.items()) + list(Q.QC.items())]
    cases += [(n, t, Q.QR_PARAMS.get(n)) for n, t in Q.QR.items()]
    cases += [(n, t, Q.QIC_PARAMS.get(n)) for n, t in Q.QIC.items()]
    gopt = GOpt(ms, backend="numpy")
    pre = {n: _rows(gopt.run(t, p)[0]) for n, t, p in cases}
    oracle = GOpt(_scratch_oracle(small_ldbc, ms), backend="numpy")
    gopt.compact()
    for n, t, p in cases:
        post = _rows(gopt.run(t, p)[0])
        # exactness: compacted store answers identically to a from-scratch
        # build over the same logical graph (same canonical renumbering,
        # so even bare-vertex-id columns like ic5's RETURN f agree)
        assert post == _rows(oracle.run(t, p)[0]), n
        if n not in Q.QIC:
            # QT/QR/QC return only properties/aggregates -> row-identical
            # across compaction; QIC may return raw vertex ids, which
            # compaction legitimately renumbers
            assert post == pre[n], n


def test_stale_snapshot_raises_after_compaction():
    base, ms = _mutable()
    ms.insert_vertex("PERSON", {"id": 9999})
    gopt = GOpt(ms, backend="numpy")
    snap = gopt.snapshot()
    ms.compact()
    assert snap.retired
    with pytest.raises(StaleSnapshotError):
        gopt.run(QK, snapshot=snap)


def test_stats_epoch_recost_with_overlay():
    """Overlay occupancy reaches the cost model: delta edges count toward
    triple frequencies, and ``GOpt.compact`` bumps the stats epoch so
    cached plans are invalidated for re-costing."""
    base, ms = _mutable()
    kt = _knows(base)
    gopt = GOpt(ms, backend="numpy")
    f0 = gopt.stats.triple_freq(kt)
    off = base.v_offset["PERSON"]
    added = sum(ms.insert_edge(kt, off + i, off + ((i + 25) % 50))
                for i in range(10))
    assert added > 0
    assert gopt.stats.triple_freq(kt) == f0 + added
    gopt.prepare(QK)
    info0 = gopt.plan_cache_info()
    assert info0["plans"] == 1
    ev = gopt.compact()
    assert ev["merged_edges"] == added
    info1 = gopt.plan_cache_info()
    assert info1["epoch"] == info0["epoch"] + 1 and info1["plans"] == 0
    assert gopt.stats.triple_freq(kt) == f0 + added   # merged into the base


# ----------------------------------------------------- pow2 capacity plateau
def test_delta_adj_pow2_capacity_plateau():
    """Delta view capacities ride pow2 buckets: growing the overlay one
    edge at a time yields O(log n) distinct (row_cap, nnz_cap) shapes, so
    device uploads / compiled programs plateau instead of thrashing."""
    keys = np.zeros(0, np.int64)
    shapes = set()
    for n in range(1, 200):
        keys = np.arange(n, dtype=np.int64) % 37
        nbrs = np.arange(n, dtype=np.int64)
        adj = _build_adj(keys, nbrs, None)
        assert adj.row_cap & (adj.row_cap - 1) == 0
        assert adj.nnz_cap & (adj.nnz_cap - 1) == 0
        shapes.add((adj.row_cap, adj.nnz_cap))
    assert len(shapes) <= 16, shapes


def test_delta_views_cached_until_touched():
    """Snapshot views keep object identity across snapshots while their
    triple is untouched (id()-keyed device caches stay warm)."""
    base, ms = _mutable()
    kt = _knows(base)
    pt = next(t for t in base.out_csr if t.label == "PURCHASES")
    off = base.v_offset["PERSON"]
    ms.insert_edge(kt, off, off + 9)
    s1 = ms.snapshot()
    ms.insert_edge(pt, off, base.v_offset["PRODUCT"])
    s2 = ms.snapshot()
    assert s2.ins[(kt, "out")] is s1.ins[(kt, "out")]
    ms.insert_edge(kt, off + 1, off + 8)
    s3 = ms.snapshot()
    assert s3.ins[(kt, "out")] is not s1.ins[(kt, "out")]


# ------------------------------------------------------ property-based tests
@st.composite
def _mutation_script(draw):
    return draw(st.lists(
        st.tuples(st.integers(0, 3), st.integers(0, 49), st.integers(0, 49)),
        min_size=1, max_size=40))


@given(script=_mutation_script())
@settings(max_examples=20, deadline=None)
def test_prop_compaction_roundtrip(script):
    """Property: any insert/delete sequence compacts to exactly the
    from-scratch build_store oracle."""
    base = generate_motivating(n_person=30, n_product=10, n_place=5)
    ms = MutableGraphStore(base)
    kt = _knows(base)
    off, n_p = base.v_offset["PERSON"], base.v_count["PERSON"]
    live = list(range(off, off + n_p))
    for op, a, b in script:
        if op == 0:
            live.append(ms.insert_vertex("PERSON", {"id": 50_000 + a}))
        elif op == 1 and len(live) > 2:
            ms.insert_edge(kt, live[a % len(live)], live[b % len(live)])
        elif op == 2 and len(live) > 2:
            ms.delete_edge(kt, live[a % len(live)], live[b % len(live)])
        elif op == 3 and len(live) > n_p // 2:
            ms.delete_vertex(live.pop(a % len(live)))
    oracle = _scratch_oracle(base, ms)
    ms.compact()
    _assert_stores_identical(ms.base, oracle)


@given(rows=st.integers(1, 40), seed=st.integers(0, 2**31 - 1),
       shards=st.sampled_from([1, 2, 4, 8]))
@settings(max_examples=25, deadline=None)
def test_prop_reassemble_csr_roundtrip(rows, seed, shards):
    """Property: partition_csr -> reassemble_csr is the identity on any
    random CSR (with and without a pos column)."""
    from repro.graphdb.partition import partition_csr, reassemble_csr
    from repro.graphdb.storage import CSR
    rng = np.random.default_rng(seed)
    deg = rng.integers(0, 6, size=rows)
    indptr = np.zeros(rows + 1, dtype=np.int64)
    indptr[1:] = np.cumsum(deg)
    nnz = int(indptr[-1])
    indices = np.sort(rng.integers(0, 100, size=nnz)).astype(np.int64)
    pos = rng.permutation(nnz).astype(np.int64) if rng.integers(2) else None
    csr = CSR(indptr=indptr, indices=indices, pos=pos)
    ip, ix, ps = reassemble_csr(partition_csr(csr, shards))
    np.testing.assert_array_equal(ip, indptr)
    np.testing.assert_array_equal(ix, indices)
    if pos is None:
        assert ps is None
    else:
        np.testing.assert_array_equal(ps, pos)


def test_reassemble_csr_roundtrip_seeded():
    """Non-hypothesis twin of the property test (always runs)."""
    from repro.graphdb.partition import partition_csr, reassemble_csr
    from repro.graphdb.storage import CSR
    rng = np.random.default_rng(3)
    for rows, shards in [(1, 1), (5, 2), (17, 4), (40, 8), (8, 8)]:
        deg = rng.integers(0, 6, size=rows)
        indptr = np.zeros(rows + 1, dtype=np.int64)
        indptr[1:] = np.cumsum(deg)
        nnz = int(indptr[-1])
        indices = np.sort(rng.integers(0, 100, size=nnz)).astype(np.int64)
        pos = rng.permutation(nnz).astype(np.int64)
        ip, ix, ps = reassemble_csr(
            partition_csr(CSR(indptr=indptr, indices=indices, pos=pos),
                          shards))
        np.testing.assert_array_equal(ip, indptr)
        np.testing.assert_array_equal(ix, indices)
        np.testing.assert_array_equal(ps, pos)


# --------------------------------------------- satellite: binding-skew replan
def test_replan_on_binding_skew():
    """A binding whose IN-set cardinality diverges >10x from the cached
    plan's build-time value peek invalidates the entry and re-plans once;
    ``plan_cache_info()['replans']`` counts it and rows stay identical to
    an uncached compile."""
    base = generate_motivating(n_person=200, n_product=60, n_place=12)
    gopt = GOpt(base)
    q = ("MATCH (a:PERSON)-[:knows]->(b:PERSON) WHERE a.id IN $S "
         "RETURN a.id AS aid, b.id AS bid ORDER BY aid, bid")
    pq = gopt.prepare(q, params={"S": [1]})
    assert pq.peeks and pq.peeks[0][3] == 1
    pq.execute({"S": [1]})
    assert gopt.plan_cache_info()["replans"] == 0
    big = list(range(200))
    tbl, _ = pq.execute({"S": big})
    assert gopt.plan_cache_info()["replans"] == 1
    ref, _ = GOpt(base).run(q, {"S": big})
    assert _rows(tbl) == _rows(ref)
    # the re-planned entry peeked the big binding: no replan churn
    pq2 = gopt.prepare(q, params={"S": big})
    pq2.execute({"S": big})
    assert gopt.plan_cache_info()["replans"] == 1
    # similar-size bindings don't trip the threshold either
    pq2.execute({"S": list(range(150))})
    assert gopt.plan_cache_info()["replans"] == 1


# ------------------------------------------------- serving: the update stream
def test_serve_update_stream_snapshot_parity():
    """Writes ride the admission path; every read answers as-of its
    admission snapshot (frozen deep-copy oracle), and later reads see the
    landed writes."""
    base, ms = _mutable()
    kt = _knows(base)
    gopt = GOpt(ms, backend="numpy")
    srv = gopt.serve(max_wave=8)
    r0 = srv.submit(QK)
    srv.drain()
    n0 = len(_rows(r0.table))
    oracle = []
    for i in range(5):
        rq = srv.submit(QK)
        oracle.append((rq, copy.deepcopy(ms)))
        w = srv.submit_update("insert_vertex", "PERSON", {"id": 7700 + i})
        srv.drain()
        assert w.status == "done"
        srv.submit_update("insert_edge", kt, base.v_offset["PERSON"] + i,
                          w.result)
        srv.drain()
    for rq, frozen in oracle:
        ref, _ = _run(frozen, QK, "numpy")
        assert _rows(rq.table) == ref
    r1 = srv.submit(QK)
    srv.drain()
    assert len(_rows(r1.table)) == n0 + 5
    assert srv.stats.writes == 10
    srv.close()


def test_serve_stats_epoch_mid_stream():
    """Satellite: bump ``refresh_stats`` mid-stream — the server keeps
    serving with row parity, plans re-compile against the new epoch (zero
    stale-plan reuse), and the epoch's re-costing is visible in
    ``plan_cache_info``."""
    base, ms = _mutable()
    kt = _knows(base)
    gopt = GOpt(ms, backend="numpy")
    srv = gopt.serve(max_wave=4)
    ref_rows, _ = _run(copy.deepcopy(ms), QK, "numpy")
    reqs = [srv.submit(QK) for _ in range(4)]
    srv.drain()
    cbo0 = gopt.compile_counters["cbo"]
    # mid-stream: overlay occupancy changes the stats, epoch bumps
    off = base.v_offset["PERSON"]
    for i in range(8):
        ms.insert_edge(kt, off + i, off + ((i + 31) % 50))
    epoch0 = gopt.plan_cache_info()["epoch"]
    gopt.refresh_stats()
    info = gopt.plan_cache_info()
    assert info["epoch"] == epoch0 + 1 and info["plans"] == 0
    ref_rows2, _ = _run(copy.deepcopy(ms), QK, "numpy")
    reqs2 = [srv.submit(QK) for _ in range(4)]
    srv.drain()
    # parity on both sides of the bump
    for r in reqs:
        assert r.status == "done" and _rows(r.table) == ref_rows
    for r in reqs2:
        assert r.status == "done" and _rows(r.table) == ref_rows2
    # zero stale-plan reuse: the post-bump submits compiled a fresh plan
    assert gopt.compile_counters["cbo"] == cbo0 + 1
    assert gopt.plan_cache_info()["plans"] == 1
    srv.close()


def test_serve_compaction_repins_chains():
    """Acceptance: after ``QueryServer.compact()`` re-warms + re-pins hot
    plans, post-compaction waves record zero chain compiles."""
    base, ms = _mutable()
    kt = _knows(base)
    gopt = GOpt(ms, backend="jax")
    srv = gopt.serve(max_wave=4, overlap=False)
    for _ in range(3):
        srv.submit(Q2HOP)
        srv.drain()
    off = base.v_offset["PERSON"]
    for i in range(4):
        gid = ms.insert_vertex("PERSON", {"id": 7600 + i})
        ms.insert_edge(kt, off + i, gid)
    pre, _ = _run(copy.deepcopy(ms), Q2HOP, "numpy")
    ev = srv.compact()
    assert ev["repinned_plans"] >= 1
    n_waves = srv.stats.waves
    chain = srv.stats.kernels.get("compile:fused_chain", 0)
    r = srv.submit(Q2HOP)
    srv.drain()
    assert _rows(r.table) == pre
    assert srv.stats.waves > n_waves
    assert srv.stats.kernels.get("compile:fused_chain", 0) == chain, \
        srv.stats.kernels
    srv.close()


def test_explain_delta_section():
    base, ms = _mutable()
    _apply_mix(ms, base)
    gopt = GOpt(ms, backend="numpy")
    rep = gopt.explain(QK)
    assert rep.delta is not None
    txt = rep.render()
    assert "-- delta --" in txt
    assert "overlay_edges" in txt and "snapshot_spread" in txt


def test_mutation_errors():
    base, ms = _mutable()
    kt = _knows(base)
    off = base.v_offset["PERSON"]
    with pytest.raises(KeyError):
        ms.insert_vertex("NOPE")
    with pytest.raises(ValueError):
        ms.insert_edge(kt, off, base.n_vertices + 99)   # not a live vertex
    gid = ms.insert_vertex("PERSON", {"id": 1})
    ms.delete_vertex(gid)
    with pytest.raises(ValueError):
        ms.insert_edge(kt, off, gid)                    # dead endpoint
    # duplicate insert is a no-op, delete+reinsert resurrects
    csr = base.out_csr[kt]
    row = int(np.argmax(np.diff(csr.indptr)))
    src, dst = off + row, int(csr.indices[csr.indptr[row]])
    assert not ms.insert_edge(kt, src, dst)             # already in base
    assert ms.delete_edge(kt, src, dst)
    assert ms.insert_edge(kt, src, dst)                 # resurrect
    assert not ms.delete_edge(kt, off, off)             # never existed
