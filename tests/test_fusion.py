"""Single-dispatch fused chain execution (DESIGN.md §8): fused-vs-unfused
parity across the Appendix-A query sets, the one-dispatch-per-chain counter
contract, pow2 shape bucketing bounding the jit cache, capacity
overflow/regrow, the fused WCOJ tail, batched execute_many tails, the
widened SUM/AVG accumulation, and the PROFILE SYNC surface."""
import numpy as np
import pytest

from benchmarks import queries as Q
from repro.core import ir
from repro.core.physical import ExpandChainNode, plan_operators
from repro.core.physical_spec import get_spec
from repro.graphdb.chain import build_chain_spec


def _table_eq(a, b, msg=""):
    assert a.nrows == b.nrows, f"{msg}: {a.nrows} != {b.nrows}"
    assert set(a.cols) == set(b.cols), msg
    for k in a.cols:
        np.testing.assert_array_equal(a.cols[k], b.cols[k],
                                      err_msg=f"{msg}/{k}")


def _fused_dispatches(stats) -> int:
    return (stats.kernels or {}).get("dispatch:fused_chain", 0)


_ALL_SETS = [("ic", Q.QIC, Q.QIC_PARAMS), ("cbo", Q.QC, {}),
             ("rbo", Q.QR, Q.QR_PARAMS), ("typeinf", Q.QT, {})]
_ALL_QUERIES = [(f"{sn}/{name}", text, params.get(name))
                for sn, qs, params in _ALL_SETS
                for name, text in qs.items()]


# ------------------------------------------------------- fused/unfused parity

@pytest.mark.parametrize("name,text,params", _ALL_QUERIES,
                         ids=[q[0] for q in _ALL_QUERIES])
def test_fused_parity_all_appendix_queries(gopt_small, name, text, params):
    """Acceptance: for every Appendix-A query, the fused-dispatch execution
    is row-identical to the per-hop loop and to the numpy backend, and
    fusion is pure packaging: unfusing the jax plan recovers exactly the
    plan the optimizer built with physical rules disabled."""
    from repro.core.physical import plan_signature, unfuse_chains
    o_np = gopt_small.optimize(text, params, backend="numpy")
    o_jx = gopt_small.optimize(text, params, backend="jax")
    o_raw = gopt_small.optimize(text, params, backend="jax",
                                physical_rules=False)
    assert plan_signature(unfuse_chains(o_jx.physical)) == \
        plan_signature(o_raw.physical)
    ref, _ = gopt_small.execute(o_np, backend="numpy")
    warm, _ = gopt_small.execute(o_jx, backend="jax")    # measuring run
    fused, fstats = gopt_small.execute(o_jx, backend="jax")
    loop, _ = gopt_small.execute(o_jx, backend="jax", chain_dispatch=False)
    _table_eq(ref, warm, name)
    _table_eq(ref, fused, name)
    _table_eq(ref, loop, name)
    nchains = sum(isinstance(n, ExpandChainNode)
                  for n in plan_operators(o_jx.physical))
    # once warmed, a chain dispatches fused at most once per chain; chains
    # outside the fusable envelope (or past the interpret-mode volume
    # cutoff) stay on the loop.  The dispatch-bound ic point queries are
    # in-envelope and MUST dispatch fused.
    assert _fused_dispatches(fstats) <= nchains, fstats.kernels
    if name in ("ic/ic1", "ic/ic3", "ic/ic11", "ic/ic12"):
        assert nchains and _fused_dispatches(fstats) == nchains, \
            fstats.kernels


# ------------------------------------------------ single-dispatch 3-hop chain

THREE_HOP = ("MATCH (a:PERSON)-[:KNOWS*3]-(z:PERSON) "
             "WHERE a.id = $pid RETURN count(z) AS c")


def test_multi_hop_chain_single_dispatch(gopt_small):
    """Acceptance: a >=3-hop Appendix-A chain (ic12: friend -> comment ->
    post -> tag -> tagclass) executes in exactly ONE device dispatch on the
    jax backend — no per-hop expand launches — row-identical to numpy."""
    opt = gopt_small.optimize(Q.QIC["ic12"], Q.QIC_PARAMS["ic12"],
                              backend="jax")
    chains = [n for n in plan_operators(opt.physical)
              if isinstance(n, ExpandChainNode)]
    assert len(chains) == 1 and len(chains[0].steps) >= 3
    ref, _ = gopt_small.execute(opt, backend="numpy")
    gopt_small.execute(opt, backend="jax")               # measuring run
    tbl, stats = gopt_small.execute(opt, backend="jax")
    _table_eq(ref, tbl)
    assert _fused_dispatches(stats) == 1, stats.kernels
    assert (stats.kernels or {}).get("dispatch:expand", 0) == 0


def test_volume_bound_chain_stays_on_loop(gopt_small):
    """Under CPU interpret, a chain whose capacities outgrow the volume
    cutoff keeps the per-hop loop (fusion's win is dispatch arithmetic) —
    still row-identical to numpy."""
    opt = gopt_small.optimize(THREE_HOP, {"pid": 5}, backend="jax",
                              cbo=False)
    ref, _ = gopt_small.execute(opt, backend="numpy")
    gopt_small.execute(opt, backend="jax")               # measuring run
    tbl, stats = gopt_small.execute(opt, backend="jax")
    _table_eq(ref, tbl)
    assert _fused_dispatches(stats) == 0, stats.kernels


# ------------------------------------------------------------- wcoj tail step

TRIANGLE = ("Match (a:PERSON)-[:KNOWS]->(b:PERSON)-[:KNOWS]->(c:PERSON), "
            "(a)-[:KNOWS]->(c) Return count(a) AS t")


def test_chain_with_wcoj_tail_single_dispatch(gopt_small):
    """A chain ending in an expand-and-intersect folds the membership
    probes into the fused program: one dispatch, no separate intersect
    launches, parity with numpy."""
    opt = gopt_small.optimize(TRIANGLE, backend="jax", cbo=False)
    chains = [n for n in plan_operators(opt.physical)
              if isinstance(n, ExpandChainNode)]
    assert chains and chains[-1].steps[-1].intersect_edges
    ref, _ = gopt_small.execute(opt, backend="numpy")
    gopt_small.execute(opt, backend="jax")               # measuring run
    tbl, stats = gopt_small.execute(opt, backend="jax")
    _table_eq(ref, tbl)
    assert _fused_dispatches(stats) == 1, stats.kernels
    assert (stats.kernels or {}).get("dispatch:intersect", 0) == 0
    loop, _ = gopt_small.execute(opt, backend="jax", chain_dispatch=False)
    _table_eq(ref, loop)


# --------------------------------------------------- folded edge predicates

EDGE_PRED_Q = ("Match (a:PERSON)-[k:KNOWS]->(b:PERSON)-[k2:KNOWS]->"
               "(c:PERSON) Where k2.creationDate >= 3 and b.id <> 7 "
               "Return count(a) AS n")


def test_chain_folds_edge_property_predicates(gopt_small):
    """Edge-property predicates (eprop refs: '#t'-offset + '#p'-position
    gathers inside the fused program) fold into their hop and stay
    row-identical to the numpy path."""
    opt = gopt_small.optimize(EDGE_PRED_Q, backend="jax", cbo=False)
    assert any(isinstance(n, ExpandChainNode)
               for n in plan_operators(opt.physical))
    ref, _ = gopt_small.execute(opt, backend="numpy")
    gopt_small.execute(opt, backend="jax")               # measuring run
    tbl, stats = gopt_small.execute(opt, backend="jax")
    _table_eq(ref, tbl)
    assert _fused_dispatches(stats) == 1, stats.kernels


# -------------------------------------------------- jit-cache size bounding

JITTER_Q = ("MATCH (p:PERSON)-[:KNOWS]->(f:PERSON)-[:KNOWS]->(g:PERSON) "
            "WHERE p.id IN $S RETURN count(p) AS c")


def test_bucketing_bounds_compile_cache(gopt_small):
    """Acceptance: jittered input sizes inside one pow2 bucket hit one
    compiled program — the compile counter plateaus after warmup while the
    dispatch counter keeps climbing."""
    ops = get_spec("jax").operators(gopt_small.store)
    # the peek binding steers the CBO to the selective chain anchor (Scan(p)
    # -> +f -> +g); execution bindings are late-bound as usual
    peek = {"S": list(range(15))}
    pq = gopt_small.prepare(JITTER_Q, peek, backend="jax")
    assert any(isinstance(n, ExpandChainNode)
               for n in plan_operators(pq.physical))
    ref_pq = gopt_small.prepare(JITTER_Q, peek, backend="numpy")
    # warm with the largest frontier so the capacity schedule covers the
    # jittered sizes (sizes 12..15 share the pow2-16 input bucket)
    big = {"S": list(range(15))}
    t, _ = pq.execute(big)
    _table_eq(ref_pq.execute(big)[0], t)
    mark = ops.kernel_stats.mark()
    sizes = (12, 13, 14, 15)
    for k in sizes:
        b = {"S": list(range(k))}
        t, _ = pq.execute(b)
        _table_eq(ref_pq.execute(b)[0], t, f"S={k}")
    compiles = ops.kernel_stats.count("compile", "fused_chain", since=mark)
    dispatches = ops.kernel_stats.count("dispatch", "fused_chain",
                                        since=mark)
    assert dispatches == len(sizes)
    assert compiles <= 1, (compiles, dispatches)   # flat across the bucket


def test_capacity_overflow_regrows_and_stays_correct(gopt_small):
    """An execution whose totals overflow the learned capacity schedule
    falls back to the loop (row-identical) and regrows the buckets; the
    next execution at that size dispatches fused again."""
    peek = {"S": list(range(15))}
    pq = gopt_small.prepare(JITTER_Q, peek, backend="jax")
    ref_pq = gopt_small.prepare(JITTER_Q, peek, backend="numpy")
    small, big = {"S": [1]}, {"S": list(range(60))}
    t, _ = pq.execute(small)                      # measuring run, tiny caps
    _table_eq(ref_pq.execute(small)[0], t)
    t, _ = pq.execute(small)                      # fused at tiny caps
    _table_eq(ref_pq.execute(small)[0], t)
    t, _ = pq.execute(big)                        # overflow -> loop, regrow
    _table_eq(ref_pq.execute(big)[0], t)
    ops = get_spec("jax").operators(gopt_small.store)
    mark = ops.kernel_stats.mark()
    t, stats = pq.execute(big)                    # fused at regrown caps
    _table_eq(ref_pq.execute(big)[0], t)
    assert ops.kernel_stats.count("dispatch", "fused_chain", since=mark) == 1


# ------------------------------------------------------------ chain spec edge

def test_chain_spec_memoized_on_plan_node(gopt_small):
    """The ChainSpec is built once per plan node and reused across engines
    (prepared-query serving): repeated executions share one handle."""
    opt = gopt_small.optimize(Q.QIC["ic1"], {"pid": 5}, backend="jax")
    node = next(n for n in plan_operators(opt.physical)
                if isinstance(n, ExpandChainNode))
    gopt_small.execute(opt, backend="jax", params={"pid": 5})
    key, spec = node.__dict__["_chain_spec"]
    assert spec is not None
    gopt_small.execute(opt, backend="jax", params={"pid": 5})
    assert node.__dict__["_chain_spec"][1] is spec


def test_numpy_backend_has_no_chain_capability(gopt_small):
    ops = get_spec("numpy").operators(gopt_small.store)
    assert not getattr(ops, "supports_chains", False)
    opt = gopt_small.optimize(Q.QIC["ic1"], {"pid": 5}, backend="jax")
    node = next(n for n in plan_operators(opt.physical)
                if isinstance(n, ExpandChainNode))
    spec = build_chain_spec(gopt_small.store,
                            gopt_small.store.triple_index(),
                            opt.logical.pattern(), node)
    assert ops.chain_program(spec) is None


# ------------------------------------------------- batched execute_many tails

@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_execute_many_stacked_tails_parity(gopt_small, backend):
    """The segmented tail stack is row-identical to the per-binding loop on
    a group+order+limit query, and runs ONE grouped reduction for the whole
    batch instead of one per binding."""
    bindings = [{"pid": p} for p in (3, 5, 9)]
    pq = gopt_small.prepare(Q.QIC["ic1"], backend=backend)
    loop = pq.execute_many(bindings, batch=False)
    ops = get_spec(backend).operators(gopt_small.store)
    calls = {"n": 0}
    orig = type(ops).group_reduce

    def spy(self, *a, **k):
        calls["n"] += 1
        return orig(self, *a, **k)

    try:
        type(ops).group_reduce = spy
        batched = pq.execute_many(bindings)
    finally:
        type(ops).group_reduce = orig
    assert calls["n"] == 1, "tails must stack into one grouped reduction"
    assert len(batched) == len(loop) == len(bindings)
    for (lt, _), (bt, bstats) in zip(loop, batched):
        _table_eq(lt, bt)
        assert any(n == "BATCH_BIND" for n, _ in bstats.op_rows)


def test_execute_many_stacked_empty_binding(gopt_small):
    """A binding matching nothing keeps the loop path's host-side result
    semantics (COUNT() over empty input) inside a stacked batch."""
    bindings = [{"pid": 5}, {"pid": 10**9}, {"pid": 3}]
    pq = gopt_small.prepare(THREE_HOP, backend="jax")
    loop = pq.execute_many(bindings, batch=False)
    batched = pq.execute_many(bindings)
    for (lt, _), (bt, _) in zip(loop, batched):
        _table_eq(lt, bt)


# ----------------------------------------- one-pass re-filter of a batch

_KNOWS = "MATCH (p:PERSON)-[:KNOWS]->(f:PERSON) WHERE "
_NONE = 10**9        # an id no vertex has
# (query, distinct bindings); the server pads a wave to a power of two
# with copies of its first binding, and so does the test
_REFILTER_CASES = {
    "eq_k2": (_KNOWS + "p.id = $pid RETURN p.id, f.id",
              [{"pid": p} for p in (3, 5)]),
    "eq_k3": (_KNOWS + "p.id = $pid RETURN p.id, f.id",
              [{"pid": p} for p in (3, 5, 9)]),
    "eq_k4": (_KNOWS + "p.id = $pid RETURN p.id, f.id",
              [{"pid": p} for p in (3, 5, 9, 12)]),
    "eq_k8": (_KNOWS + "p.id = $pid RETURN p.id, f.id",
              [{"pid": p} for p in (3, 5, 9, 12, 17, 20, 25, 31)]),
    "param_lhs": (_KNOWS + "$pid = p.id RETURN p.id, f.id",
                  [{"pid": p} for p in (5, 3, 9)]),
    "lt": ("MATCH (p:PERSON) WHERE p.id < $x RETURN p.id",
           [{"x": v} for v in (4, 9, 2)]),
    "ne_and_lit": (_KNOWS + "p.id <> $pid AND f.id < 30 RETURN p.id, f.id",
                   [{"pid": p} for p in (3, 5)]),
    "or_lit": (_KNOWS + "p.id = $pid OR p.id = 7 RETURN p.id, f.id",
               [{"pid": p} for p in (3, 5, 9)]),
    "not_and_lit": (_KNOWS + "NOT p.id = $pid AND p.id < 12 "
                    "RETURN p.id, f.id", [{"pid": p} for p in (3, 5)]),
    "mixed_empty": (_KNOWS + "p.id = $pid RETURN p.id, f.id",
                    [{"pid": 5}, {"pid": _NONE}, {"pid": 3}]),
    "all_empty": (_KNOWS + "p.id = $pid RETURN p.id, f.id",
                  [{"pid": _NONE}, {"pid": _NONE + 1}, {"pid": _NONE + 2}]),
    # the unions of p.id and f.id keep rows that no single binding does
    # (3 knows 10 but not 6, 5 knows 6 but not 10)
    "all_empty_after_union": (
        _KNOWS + "p.id = $a AND f.id = $b RETURN p.id, f.id",
        [{"a": 3, "b": 6}, {"a": 5, "b": 10}]),
    "mixed_empty_after_union": (
        _KNOWS + "p.id = $a AND f.id = $b RETURN p.id, f.id",
        [{"a": 3, "b": 6}, {"a": 5, "b": 10}, {"a": 3, "b": 10}]),
    "string": ("MATCH (p:PERSON) WHERE p.firstName = $name RETURN p.id",
               [{"name": n} for n in ("Jan", "Li", "Nobody")]),
    "no_param": ("MATCH (p:PERSON)-[:KNOWS]->(f:PERSON) WHERE p.id < 9 "
                 "RETURN p.id, f.id", [{}, {}]),
    "in_set": (_KNOWS + "p.id IN $ids RETURN p.id, f.id",
               [{"ids": [3, 5, 9]}, {"ids": [12, 17, 20]},
                {"ids": [5, 25, 31]}]),
}


def _pow2_padded(bindings):
    k = 1 << (len(bindings) - 1).bit_length()
    return bindings + [bindings[0]] * (k - len(bindings))


@pytest.mark.parametrize("case", sorted(_REFILTER_CASES))
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_execute_many_batched_refilter_parity(gopt_small, backend, case):
    """The stacked tail's one-pass re-filter (one (bindings x rows) mask,
    one compaction) is row-identical to executing each binding alone, for
    broadcast predicates, stacked ones (``IN $set``, ``$param = expr``)
    and empty bindings;
    the jax backend fetches its per-binding counts in one round trip."""
    text, distinct = _REFILTER_CASES[case]
    bindings = _pow2_padded(distinct)
    pq = gopt_small.prepare(text, backend=backend)
    loop = pq.execute_many(bindings, batch=False)
    batched = pq.execute_many(bindings)
    assert len(batched) == len(loop) == len(bindings)
    for i, ((lt, _), (bt, bstats)) in enumerate(zip(loop, batched)):
        _table_eq(lt, bt, f"{case}[{i}]")
        assert bstats.fallbacks == {}, bstats.fallbacks
    if backend == "jax":
        refilters = batched[0][1].kernels.get("sync:refilter", 0)
        assert refilters == (0 if case == "all_empty" else 1)


_UNION_PREDS = {
    "eq": ir.Cmp("=", ir.Prop("p", "id"), ir.Param("pid")),
    "or_lit": ir.BoolOp("OR", (ir.Cmp("<", ir.Prop("p", "id"),
                                      ir.Param("pid")),
                               ir.Cmp("=", ir.Prop("p", "id"), ir.Lit(40)))),
    "in_set": ir.InSet(ir.Prop("p", "id"), ir.Param("ids")),
}


@pytest.mark.parametrize("pred", sorted(_UNION_PREDS))
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_union_mask_is_or_of_binding_masks(small_ldbc, backend, pred):
    """The pattern phase's union filter is the OR over bindings of each
    binding's own mask."""
    from repro.graphdb.engine import Engine
    eng = Engine(small_ldbc, backend=backend)
    lo, hi = small_ldbc.type_range("PERSON")
    tbl = eng._table({"p": eng.ops.scan(lo, hi)}, hi - lo)
    bound = [{"pid": 3, "ids": [3, 9]}, {"pid": 17, "ids": [12]},
             {"pid": 3, "ids": [3, 9]}, {"pid": _NONE, "ids": [_NONE]}]
    e = _UNION_PREDS[pred]
    eng._batch = bound
    got = np.asarray(eng.ops.to_host(eng._union_mask(tbl, e)))
    want = np.zeros(hi - lo, bool)
    for b in bound:
        eng._params = b
        want |= np.asarray(eng.ops.to_host(eng._eval(tbl, e))).astype(bool)
    assert got.dtype == np.bool_
    np.testing.assert_array_equal(got, want)
    assert got.any()


# --------------------------------------------------- widened SUM/AVG on device

def test_group_sum_avg_widened_at_hub_scale(small_ldbc):
    """Regression (ROADMAP follow-up): group SUM/AVG must stay exact when
    the *running total across groups* exceeds what float32/int32 cumsum can
    carry — the magnitudes where the naive implementation drifted."""
    jops = get_spec("jax").operators(small_ldbc)
    nops = get_spec("numpy").operators(small_ldbc)
    rng = np.random.default_rng(7)
    n = 120_000
    keys = np.sort(rng.integers(0, 97, n))
    vals = rng.integers(100_000, 900_000, n)     # running total ~6e10
    first_n, ref = nops.group_reduce(keys, {"s": ("SUM", vals),
                                            "a": ("AVG", vals)})
    first_j, got = jops.group_reduce(jops.asarray(keys),
                                     {"s": ("SUM", jops.asarray(vals)),
                                      "a": ("AVG", jops.asarray(vals))})
    np.testing.assert_array_equal(np.asarray(jops.to_host(got["s"])),
                                  ref["s"])      # SUM exact
    np.testing.assert_allclose(np.asarray(jops.to_host(got["a"])),
                               ref["a"], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(jops.to_host(first_j)),
                                  first_n)


def test_group_sum_negative_and_mixed_values(small_ldbc):
    jops = get_spec("jax").operators(small_ldbc)
    nops = get_spec("numpy").operators(small_ldbc)
    rng = np.random.default_rng(3)
    keys = np.sort(rng.integers(0, 11, 5000))
    vals = rng.integers(-(2**30), 2**30, 5000) // max(1, 5000 // 11)
    _, ref = nops.group_reduce(keys, {"s": ("SUM", vals)})
    _, got = jops.group_reduce(jops.asarray(keys),
                               {"s": ("SUM", jops.asarray(vals))})
    np.testing.assert_array_equal(np.asarray(jops.to_host(got["s"])),
                                  ref["s"])


# ------------------------------------------------------------- PROFILE SYNC

def test_profile_sync_reports_device_times(gopt_small):
    rep = gopt_small.explain(Q.QIC["ic3"], Q.QIC_PARAMS["ic3"],
                             analyze=True, sync=True, backend="jax")
    assert rep.sync and rep.analyze
    assert all(o.actual_time_s is not None and o.actual_time_s >= 0
               for o in rep.operators)
    assert rep.render().startswith("PROFILE SYNC")


def test_profile_sync_prefix_routes(gopt_small):
    rep = gopt_small.run("PROFILE SYNC " + Q.QT["Qt2"], backend="jax")
    assert rep.sync and rep.analyze
    plain = gopt_small.run("PROFILE " + Q.QT["Qt2"], backend="jax")
    assert plain.analyze and not plain.sync


def test_profile_sync_parser_hint(gopt_small):
    from repro.core.parser import parse_cypher
    plan = parse_cypher("PROFILE SYNC " + Q.QT["Qt2"], gopt_small.schema)
    assert plan.hints["explain"] == "profile_sync"
    rep = gopt_small.run(plan)
    assert rep.sync and rep.analyze
