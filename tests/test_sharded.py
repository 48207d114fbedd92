"""Sharded multi-device backend (DESIGN.md §10): CSR partitioning,
operator conformance on a device mesh, Appendix-A row parity vs numpy,
the ExchangeStats ledger + EXPLAIN surface, the cost model's exchange
term, the devices= spec pinning, and the streamed LDBC generator.

Shard counts adapt to the devices jax actually exposes: run standalone
(``pytest tests/test_sharded.py``) this module fakes an 8-device CPU mesh
via XLA_FLAGS *before jax's first import*; inside the full suite an
earlier module usually imported jax already and the mesh is 1 device —
every assertion here holds at any world size (collectives over a world of
1 still execute and record).
"""
import os
import sys
import types

if "jax" not in sys.modules:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import numpy as np
import pytest

from benchmarks import queries as Q
from repro.core.cardinality import CardEstimator
from repro.core.cbo import GraphOptimizer
from repro.core.gopt import GOpt
from repro.core.physical_spec import (ExchangeStats, TransferStats,
                                      get_spec, validate_operator_set)
from repro.graphdb.partition import (CsrShards, partition_csr,
                                     reassemble_csr)


def _table_eq(a, b):
    assert a.nrows == b.nrows
    assert set(a.cols) == set(b.cols)
    for k in a.cols:
        np.testing.assert_array_equal(a.cols[k], b.cols[k], err_msg=k)


def _fresh_ops(store, devices=None):
    """A NEW operator instance (spec.operators memoizes per store)."""
    from repro.graphdb.sharded_backend import ShardedOperators
    return ShardedOperators(store, devices=devices)


# --------------------------------------------------------------- partition


def _csr(indptr, indices, pos=None):
    return types.SimpleNamespace(indptr=np.asarray(indptr, np.int64),
                                 indices=np.asarray(indices, np.int64),
                                 pos=None if pos is None
                                 else np.asarray(pos, np.int64))


@pytest.mark.parametrize("n_shards", [1, 2, 4, 8])
@pytest.mark.parametrize("with_pos", [False, True])
def test_partition_roundtrip(n_shards, with_pos):
    rng = np.random.default_rng(11)
    n_rows = 13
    deg = rng.integers(0, 7, n_rows)
    indptr = np.concatenate([[0], np.cumsum(deg)])
    indices = rng.integers(0, 50, int(indptr[-1]))
    pos = rng.permutation(int(indptr[-1])) if with_pos else None
    sh = partition_csr(_csr(indptr, indices, pos), n_shards)
    ip2, ix2, ps2 = reassemble_csr(sh)
    np.testing.assert_array_equal(ip2, indptr)
    np.testing.assert_array_equal(ix2, indices)
    if with_pos:
        np.testing.assert_array_equal(ps2, pos)
    else:
        assert ps2 is None


def test_partition_ownership_and_bases():
    indptr = [0, 2, 5, 5, 6, 9, 9, 10]          # 7 rows
    sh = partition_csr(_csr(indptr, np.arange(10)), 4)
    assert sh.rows_per_shard == 2
    owners = sh.owner_of(np.arange(7))
    assert owners.tolist() == [0, 0, 1, 1, 2, 2, 3]
    # edge_base[s] is the global flat position of the shard's first edge
    assert sh.edge_base.tolist() == [0, 5, 6, 9]
    # empty / short shards carry inert degree-0 padded rows
    assert sh.indptr[3].tolist()[:2] == [0, 1]


def test_partition_more_shards_than_rows():
    sh = partition_csr(_csr([0, 2, 5, 5, 6], [10, 12, 3, 7, 9, 12]), 8)
    assert sh.rows_per_shard == 1
    ip2, ix2, _ = reassemble_csr(sh)
    np.testing.assert_array_equal(ip2, [0, 2, 5, 5, 6])
    np.testing.assert_array_equal(ix2, [10, 12, 3, 7, 9, 12])


# ------------------------------------------------------------- conformance


def test_sharded_conformance(small_ldbc):
    ops = _fresh_ops(small_ldbc)
    validate_operator_set(ops, conformance=True)
    # the pattern collectives were recorded (expand runs even at S=1)
    assert ops.exchange_stats.count(kind="psum") > 0


def test_exchange_stats_ledger():
    es = ExchangeStats()
    es.record("psum", "expand_frontier", 64)
    es.record("all_gather", "join", 128)
    es.record("all_gather", "join", 128)
    assert es.count() == 3
    assert es.count(kind="all_gather") == 2
    assert es.elems(label="join") == 256
    m = es.mark()
    es.record("pmin", "group_reduce", 16)
    assert es.count(since=m) == 1
    assert es.summary(m) == {"pmin:group_reduce": {"calls": 1, "elems": 16}}
    es.reset()
    assert es.count() == 0 and es.summary() == {}


# ------------------------------------------------- end-to-end query parity

PARITY = [
    ("ic1", Q.QIC["ic1"], Q.QIC_PARAMS["ic1"]),   # 2-hop + group/order
    ("Qc1a", Q.QC["Qc1a"], None),                 # cycle via intersect
    ("Qr2", Q.QR["Qr2"], None),                   # RBO rewrites
    ("Qt1", Q.QT["Qt1"], None),                   # type inference
    ("ic5", Q.QIC["ic5"], Q.QIC_PARAMS["ic5"]),   # join-heavy
]


@pytest.mark.parametrize("name,text,params", PARITY,
                         ids=[p[0] for p in PARITY])
def test_sharded_appendix_parity(gopt_small, name, text, params):
    opt = gopt_small.optimize(text, params, backend="sharded")
    ref, _ = gopt_small.execute(opt, backend="numpy")
    tbl, stats = gopt_small.execute(opt, backend="sharded")
    _table_eq(ref, tbl)
    # the distributed residency contract: collectives recorded on-device,
    # zero mid-plan host transfers, one host gather at delivery
    assert stats.exchanges, "no collective exchanges recorded"
    assert TransferStats.mid_plan_d2h(stats.transfers) == 0, stats.transfers
    if tbl.nrows:
        assert stats.transfers.get("deliver:d2h", {}).get("calls", 0) > 0


def test_sharded_expand_records_frontier_exchange(gopt_small):
    _, stats = gopt_small.run(Q.QIC["ic1"], params=Q.QIC_PARAMS["ic1"],
                              backend="sharded")
    assert "psum:expand_frontier" in stats.exchanges
    assert "psum_scatter:expand_emit" in stats.exchanges


def test_sharded_blowup_guard(small_ldbc):
    ops = _fresh_ops(small_ldbc)
    from repro.core.physical_spec import _conf_csr
    csr = _conf_csr()
    with pytest.raises(RuntimeError, match="blow-up"):
        ops.expand(csr, ops.asarray(np.array([1, 0, 2, 3])), max_out=2)


def test_profile_renders_exchange_section(gopt_small):
    pq = gopt_small.prepare(Q.QIC["ic1"], backend="sharded")
    rep = pq.explain(analyze=True, params=Q.QIC_PARAMS["ic1"])
    assert rep.exchanges
    text = rep.render()
    assert "-- exchanges --" in text
    assert "psum:expand_frontier" in text


# ---------------------------------------------------------- spec pinning


def test_devices_kwarg_pins_spec(small_ldbc):
    g = GOpt(small_ldbc, backend="sharded", devices=2)
    assert g.spec.name == "sharded[2]"
    ops = g.spec.operators(small_ldbc)
    assert ops.n_shards in (1, 2)        # clamped to available devices
    # same count -> same registered spec object (memoized)
    g2 = GOpt(small_ldbc, backend="sharded", devices=2)
    assert g2.spec is g.spec
    # pinned execution stays row-correct
    ref, _ = GOpt(small_ldbc).run(Q.QT["Qt1"])
    tbl, _ = g.run(Q.QT["Qt1"])
    _table_eq(ref, tbl)


def test_devices_kwarg_requires_sharded(small_ldbc):
    with pytest.raises(ValueError, match="sharded"):
        GOpt(small_ldbc, backend="numpy", devices=4)


# ------------------------------------------------------------- cost model


def test_cost_params_have_exchange_term():
    assert get_spec("sharded").cost.alpha_exchange > 0
    assert get_spec("jax").cost.alpha_exchange == 0.0
    assert get_spec("numpy").cost.alpha_exchange == 0.0


def test_exchange_term_raises_costs(gopt_small):
    pattern = gopt_small.parse(
        "Match (p:PERSON)-[:KNOWS]->(q:PERSON) Return p").pattern()
    est = CardEstimator(gopt_small.stats, gopt_small.glogue)
    base = GraphOptimizer(est, spec="sharded", alpha_exchange=0.0)
    dist = GraphOptimizer(est, spec="sharded")
    assert dist.alpha_exchange == get_spec("sharded").cost.alpha_exchange
    v = sorted(pattern.vertices)[0]
    edges = [e for e in pattern.edges if v in (e.src, e.dst)][:1]
    f_src = 100.0
    c0, _ = base._expand_cost(pattern, frozenset({edges[0].other(v)}),
                              f_src, v, edges)
    c1, _ = dist._expand_cost(pattern, frozenset({edges[0].other(v)}),
                              f_src, v, edges)
    assert c1 == pytest.approx(c0 + dist.alpha_exchange * f_src)


# ------------------------------------------------------ streamed generator


def test_streamed_ldbc_deterministic():
    from repro.graphdb.ldbc import generate_ldbc_streamed
    a = generate_ldbc_streamed(0.05)
    b = generate_ldbc_streamed(0.05)
    assert a.n_vertices == b.n_vertices and a.n_edges == b.n_edges
    q = ("Match (p:PERSON)-[:KNOWS]->(q:PERSON)-[:LIKES]->(m:POST) "
         "Return count(*)")
    ta, _ = GOpt(a).run(q)
    tb, _ = GOpt(b).run(q)
    _table_eq(ta, tb)
    c = generate_ldbc_streamed(0.05, seed=9)
    assert c.n_edges != a.n_edges or not np.array_equal(
        next(iter(ta.cols.values())),
        next(iter(GOpt(c).run(q)[0].cols.values())))


def test_streamed_ldbc_same_across_processes():
    """One seed, one store, whatever the process's string-hash salt."""
    import os
    import subprocess
    import sys

    from repro.graphdb.ldbc import generate_ldbc_streamed
    code = ("from repro.graphdb.ldbc import generate_ldbc_streamed as g; "
            "s = g(0.05); print(sum(int(c.indices.sum()) "
            "for c in s.out_csr.values()))")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    sums = {int(subprocess.run(
        [sys.executable, "-c", code], check=True, capture_output=True,
        text=True, env={**os.environ, "PYTHONHASHSEED": salt,
                        "PYTHONPATH": src}).stdout)
        for salt in ("1", "2")}
    s = generate_ldbc_streamed(0.05)
    sums.add(sum(int(c.indices.sum()) for c in s.out_csr.values()))
    assert len(sums) == 1, sums


def test_streamed_ldbc_runs_appendix_queries():
    from repro.graphdb.ldbc import generate_ldbc_streamed
    g = GOpt(generate_ldbc_streamed(0.05))
    tbl, _ = g.run(Q.QIC["ic1"], params=Q.QIC_PARAMS["ic1"])
    assert set(tbl.cols)           # columns delivered; rows may be few


# --------------------------------------- satellite: nonzero/distinct buckets


def test_nonzero_bucket_plateau(small_ldbc):
    """Mask/compaction compiles key on pow2 buckets, not exact lengths."""
    ops = get_spec("jax").make_operators(small_ldbc)
    jnp = ops._jnp
    ks = ops.kernel_stats
    m = ks.mark()
    for n in (17, 19, 23, 31):          # one 32-bucket
        idx = ops.nonzero(jnp.arange(n) % 3 == 0)
        assert idx.shape[0] == len([i for i in range(n) if i % 3 == 0])
    assert ks.summary(m).get("compile:nonzero", 0) == 1
    m = ks.mark()
    ops.nonzero(jnp.arange(40) % 3 == 0)   # next bucket: one new compile
    assert ks.summary(m).get("compile:nonzero", 0) == 1


def test_distinct_bucket_plateau_and_semantics(small_ldbc):
    ops = get_spec("jax").make_operators(small_ldbc)
    jnp = ops._jnp
    ks = ops.kernel_stats
    m = ks.mark()
    for vals in ([3, 1, 3, 1, 7], [5, 5, 5], [2, 9, 2, 9, 9, 4]):
        idx = np.asarray(ops.to_host(
            ops.distinct_indices(jnp.asarray(np.array(vals, np.int32)))))
        first_seen = sorted({v: i for i, v in
                             reversed(list(enumerate(vals)))}.values())
        assert idx.tolist() == first_seen
    assert ks.summary(m).get("compile:distinct", 0) == 1


def _segment_ops(kind, store):
    from repro.graphdb.faults import FaultPlan, faulty_spec
    from repro.graphdb.host_staging import HostStagingOperators
    if kind == "sharded":
        return _fresh_ops(store)
    if kind == "host_staging":
        return HostStagingOperators(get_spec("jax").make_operators(store))
    if kind.startswith("faulty:"):
        return faulty_spec(kind.split(":")[1], FaultPlan([])).operators(store)
    return get_spec(kind).make_operators(store)


@pytest.mark.parametrize("kind", ["numpy", "host_staging", "jax",
                                  "faulty:numpy", "faulty:jax", "sharded"])
def test_nonzero_segments_contract(small_ldbc, kind):
    """The batched re-filter's compaction: segment-major positions,
    ascending rows within a segment, host counts equal to the row sums,
    empty outputs for an all-False mask, on every shipped operator set."""
    ops = _segment_ops(kind, small_ldbc)
    validate_operator_set(ops, conformance=True)
    rng = np.random.default_rng(3)
    for k, n in ((1, 5), (2, 7), (4, 3), (8, 33)):
        m = rng.random((k, n)) < 0.4
        m[k // 2] = False                   # an empty segment
        seg, row, counts = ops.nonzero_segments(ops.asarray(m))
        want_seg, want_row = np.nonzero(m)
        np.testing.assert_array_equal(np.asarray(ops.to_host(seg)), want_seg)
        np.testing.assert_array_equal(np.asarray(ops.to_host(row)), want_row)
        assert isinstance(counts, np.ndarray)
        np.testing.assert_array_equal(counts, m.sum(axis=1))
    # an int 0/1 mask selects what its bool twin does (never bitwise-not)
    mi = np.array([[0, 1, 1], [0, 0, 0], [1, 0, 1]], np.int32)
    seg, row, counts = ops.nonzero_segments(ops.asarray(mi))
    np.testing.assert_array_equal(np.asarray(ops.to_host(seg)), [0, 0, 2, 2])
    np.testing.assert_array_equal(np.asarray(ops.to_host(row)), [1, 2, 0, 2])
    np.testing.assert_array_equal(counts, [2, 0, 2])
    seg, row, counts = ops.nonzero_segments(ops.asarray(np.zeros((4, 6),
                                                                 bool)))
    assert np.asarray(ops.to_host(seg)).shape == (0,)
    assert np.asarray(ops.to_host(row)).shape == (0,)
    np.testing.assert_array_equal(counts, [0, 0, 0, 0])


def test_nonzero_pad_value_inert(small_ldbc):
    """Pad slots must never leak into the selected indices."""
    ops = get_spec("jax").make_operators(small_ldbc)
    jnp = ops._jnp
    m = jnp.ones(17, bool)              # all true; pads (to 32) are False
    idx = np.asarray(ops.to_host(ops.nonzero(m)))
    assert idx.tolist() == list(range(17))
