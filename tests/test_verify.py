"""PlanVerifier (DESIGN.md §12): seeded adversarial passes each breaking one
invariant — every mutation must raise ``PlanInvariantError`` naming the
offending pass under ``verify="always"`` — plus the unsat short-circuit
regression (satellite: type-inference-unsatisfiable plans verify clean as
``verified-empty``), store-level contract unit checks, verify-mode parity
on every Appendix-A query across all three backends, and the contract
linter's clean-run gate.
"""
import types

import pytest

from benchmarks import queries as Q
from repro.core import ir
from repro.core.errors import PipelineError, PlanInvariantError
from repro.core.gopt import GOpt
from repro.core.pattern import PatternEdge
from repro.core.physical import ExpandChainNode, ExpandNode, plan_operators
from repro.core.pipeline import UNSAT_MESSAGE, Pass
from repro.core.schema import EdgeTriple, ldbc_schema
from repro.core.verify import OK, VERIFIED_EMPTY, PlanVerifier

PATH_Q = ("MATCH (p:PERSON)-[:KNOWS]->(f:PERSON)-[:ISLOCATEDIN]->(c:CITY) "
          "WHERE p.id = 5 RETURN f.id, c.name")
HOP2_Q = ("MATCH (a:PERSON)-[:KNOWS]->(b:PERSON)-[:KNOWS]->(c:PERSON) "
          "WHERE a.id = 3 RETURN c.id")
MULE_PARAMS = {"hops": 2, "S1": [1, 2, 3], "S2": [4, 5, 6]}


@pytest.fixture
def gopt(small_ldbc):
    return GOpt(small_ldbc, build_glogue=False)


def _expect_invariant(gopt, query, mutation, params=None):
    gopt.pipeline.register(mutation)
    with pytest.raises(PlanInvariantError) as exc:
        gopt.prepare(query, params, verify="always")
    assert exc.value.pass_name == mutation.name
    assert exc.value.phase == mutation.phase
    return exc.value


# --------------------------------------------------------------------------
# Seeded adversarial passes: logical-plan invariants (rbo phase)
# --------------------------------------------------------------------------


class _MutPass(Pass):
    phase = "rbo"
    done = False

    def run(self, ctx):
        if self.done:            # fire once, then let the fixpoint converge
            return False
        self.done = True
        return self.mutate(ctx)


class DropVertexPass(_MutPass):
    name = "drop_vertex"

    def mutate(self, ctx):
        pat = ctx.plan.pattern().copy()
        del pat.vertices["c"]
        ctx.plan.replace_pattern(pat)
        return True


class DanglingVarPass(_MutPass):
    name = "dangling_select"

    def mutate(self, ctx):
        ctx.plan.ops.append(ir.Select(
            ir.Cmp("=", ir.Prop("ghost", "id"), ir.Lit(1))))
        return True


class NarrowProjectPass(_MutPass):
    name = "narrow_project"

    def mutate(self, ctx):
        # slot a PROJECT keeping only `p` ahead of the query's own tail:
        # every later f.id / c.name reference now dereferences a dropped
        # alias
        ctx.plan.ops.insert(1, ir.Project([(ir.Var("p"), "p")]))
        return True


class BadPropPass(_MutPass):
    name = "bad_prop"

    def mutate(self, ctx):
        pat = ctx.plan.pattern().copy()
        pat.vertices["p"].predicates.append(
            ir.Cmp("=", ir.Prop("p", "salary"), ir.Lit(9)))
        ctx.plan.replace_pattern(pat)
        return True


class UnsatRewritePass(_MutPass):
    name = "unsat_rewrite"

    def mutate(self, ctx):
        # KNOWS is PERSON->PERSON: forcing f to CITY makes inference INVALID.
        # Because type_inference already proved this pattern satisfiable,
        # the verifier reports a violation, NOT a clean verified-empty.
        pat = ctx.plan.pattern().copy()
        pat.vertices["f"].types = frozenset({"CITY"})
        ctx.plan.replace_pattern(pat)
        return True


class RebindBakedParamPass(_MutPass):
    name = "rebind_structural"

    def mutate(self, ctx):
        # $hops was consumed structurally at build time (hop unfolding);
        # re-introducing it as a value expression is a rewrite bug
        ctx.plan.ops.append(ir.Select(
            ir.Cmp(">=", ir.Prop("p1", "id"), ir.Param("hops"))))
        return True


class RogueTriplePass(_MutPass):
    name = "rogue_triple"

    def mutate(self, ctx):
        # endpoint-consistent (PERSON->PERSON) so inference stays alive,
        # but the triple is not in the schema
        pat = ctx.plan.pattern().copy()
        e = pat.edges[0]
        e.triples = frozenset({EdgeTriple("PERSON", "SPIES_ON", "PERSON")})
        ctx.plan.replace_pattern(pat)
        return True


def test_drop_vertex_caught(gopt):
    err = _expect_invariant(gopt, PATH_Q, DropVertexPass())
    assert any(v.startswith("plan-shape:") for v in err.violations)


def test_dangling_select_caught(gopt):
    err = _expect_invariant(gopt, PATH_Q, DanglingVarPass())
    assert any(v.startswith("alias-scope:") and "ghost" in v
               for v in err.violations)


def test_narrow_project_caught(gopt):
    err = _expect_invariant(gopt, PATH_Q, NarrowProjectPass())
    assert any(v.startswith("alias-scope:") for v in err.violations)


def test_bad_prop_caught(gopt):
    err = _expect_invariant(gopt, PATH_Q, BadPropPass())
    assert any(v.startswith("schema-props:") and "salary" in v
               for v in err.violations)


def test_unsat_rewrite_caught_not_verified_empty(gopt):
    err = _expect_invariant(gopt, PATH_Q, UnsatRewritePass())
    assert any(v.startswith("satisfiability:") for v in err.violations)


def test_rebind_structural_param_caught(gopt):
    err = _expect_invariant(gopt, Q.MONEY_MULE, RebindBakedParamPass(),
                            params=MULE_PARAMS)
    assert any(v.startswith("param-bindings:") and "$hops" in v
               for v in err.violations)


def test_rogue_triple_caught(gopt):
    err = _expect_invariant(gopt, PATH_Q, RogueTriplePass())
    assert any(v.startswith("schema-edges:") and "SPIES_ON" in v
               for v in err.violations)


# --------------------------------------------------------------------------
# Seeded adversarial passes: physical-plan invariants (post_physical phase)
# --------------------------------------------------------------------------


class _PhysMutPass(Pass):
    phase = "post_physical"

    def run(self, ctx):
        return self.mutate(ctx)


class DuplicateBindPass(_PhysMutPass):
    name = "duplicate_bind"

    def mutate(self, ctx):
        for n in plan_operators(ctx.physical):
            if isinstance(n, ExpandNode):
                n.new_alias = "p"          # Scan(p) already bound it
                return True
        return False


class DropPhysicalAliasPass(_PhysMutPass):
    name = "drop_physical_alias"

    def mutate(self, ctx):
        for n in plan_operators(ctx.physical):
            if isinstance(n, ExpandNode):
                n.new_alias = "zz"         # not a pattern vertex
                return True
        return False


class ReorderChainHopsPass(_PhysMutPass):
    name = "reorder_chain_hops"

    def mutate(self, ctx):
        for n in plan_operators(ctx.physical):
            if isinstance(n, ExpandChainNode) and len(n.steps) >= 2:
                n.steps = (n.steps[1], n.steps[0])
                return True
        return False


class IntersectNotLastPass(_PhysMutPass):
    name = "intersect_not_last"

    def mutate(self, ctx):
        import dataclasses
        for n in plan_operators(ctx.physical):
            if isinstance(n, ExpandChainNode) and len(n.steps) >= 2:
                n.steps = (dataclasses.replace(
                    n.steps[0], intersect_edges=(n.steps[1].edge,)),
                    *n.steps[1:])
                return True
        return False


def test_duplicate_bind_caught(gopt):
    err = _expect_invariant(gopt, PATH_Q, DuplicateBindPass())
    assert any("re-binds" in v for v in err.violations)


def test_drop_physical_alias_caught(gopt):
    err = _expect_invariant(gopt, PATH_Q, DropPhysicalAliasPass())
    assert any(v.startswith("physical-cover:") for v in err.violations)


def test_reorder_chain_hops_caught(small_ldbc):
    g = GOpt(small_ldbc, build_glogue=False, backend="jax")
    err = _expect_invariant(g, HOP2_Q, ReorderChainHopsPass())
    assert any(v.startswith("chain-contract:")
               and "hop discontinuity" in v for v in err.violations)


def test_intersect_not_last_caught(small_ldbc):
    g = GOpt(small_ldbc, build_glogue=False, backend="jax")
    err = _expect_invariant(g, HOP2_Q, IntersectNotLastPass())
    assert any(v.startswith("chain-contract:")
               and "must come last" in v for v in err.violations)


def test_error_names_pass_and_carries_diff(gopt):
    err = _expect_invariant(gopt, PATH_Q, DropVertexPass())
    text = str(err)
    assert "drop_vertex" in text and "rbo" in text
    assert err.trace is not None
    assert err.trace.diff          # the offending rewrite's plan diff


def test_clean_pipeline_never_raises(gopt):
    rep = gopt.prepare(PATH_Q, verify="always").explain()
    assert rep.verify["status"] == OK
    assert rep.verify["violations"] == []
    assert "-- verify --" in rep.render()


# --------------------------------------------------------------------------
# Satellite: unsatisfiable queries short-circuit cleanly
# --------------------------------------------------------------------------

UNSAT_Q = "MATCH (p:PERSON)-[:KNOWS]->(c:CITY) RETURN p.id"


@pytest.mark.parametrize("mode", ["cached", "always"])
def test_unsat_is_verified_empty_not_invariant_error(gopt, mode):
    rep = gopt.prepare(UNSAT_Q, verify=mode).explain()
    assert rep.invalid
    assert rep.verify["status"] == VERIFIED_EMPTY
    assert rep.verify["violations"] == []
    out = rep.render()
    assert UNSAT_MESSAGE in out and "-- verify --" in out


def test_unsat_execution_still_empty(gopt):
    pq = gopt.prepare(UNSAT_Q, verify="always")
    tbl, _ = pq.execute()
    assert tbl.nrows == 0


# --------------------------------------------------------------------------
# Verify modes: memoization, flag plumbing, bad modes
# --------------------------------------------------------------------------


def test_cached_mode_memoizes_by_canonical_form(gopt):
    r1 = gopt.prepare(PATH_Q, verify="cached").explain().verify
    assert r1["status"] == OK and not r1["cached"]
    gopt._plan_cache.clear()
    gopt._text_cache.clear()       # force a re-optimize, same pipeline memo
    r2 = gopt.prepare(PATH_Q, verify="cached").explain().verify
    assert r2["cached"]


def test_verify_off_by_default(gopt):
    rep = gopt.prepare(PATH_Q).explain()
    assert rep.verify is None
    assert "-- verify --" not in rep.render()


def test_unknown_verify_mode_rejected(small_ldbc):
    with pytest.raises(PipelineError):
        GOpt(small_ldbc, build_glogue=False).prepare(
            PATH_Q, verify="sometimes")
    with pytest.raises(ValueError):
        GOpt(small_ldbc, build_glogue=False, verify="sometimes")


def test_gopt_instance_default_verify(small_ldbc):
    g = GOpt(small_ldbc, build_glogue=False, verify="cached")
    assert g.prepare(PATH_Q).explain().verify["status"] == OK


# --------------------------------------------------------------------------
# Store-level contract checks (unit level: synthetic ops/stores)
# --------------------------------------------------------------------------


def _verifier_with_ops(fake_ops):
    store = types.SimpleNamespace()
    store.__dict__["_physical_ops_cache"] = {"fake": fake_ops}
    return PlanVerifier(ldbc_schema(), spec=types.SimpleNamespace(name="fake"),
                        store=store)


def test_capacity_pow2_violation():
    ops = types.SimpleNamespace(
        name="fake",
        _chains={"k": types.SimpleNamespace(caps=(16, 24), _progs={})})
    v = []
    _verifier_with_ops(ops)._check_capacities(v)
    assert v and "capacity-pow2" in v[0] and "24" in v[0]


def test_capacity_monotonicity_violation():
    prog = types.SimpleNamespace(
        caps=(16, 16), _progs={((32, 16), 8, (), ()): object()})
    v = []
    _verifier_with_ops(types.SimpleNamespace(
        name="fake", _chains={"k": prog}))._check_capacities(v)
    assert v and "monotonically" in v[0]


def test_operator_contract_failures_surface():
    ops = types.SimpleNamespace(name="fake")
    ops.__dict__["_dtype_contract_failures"] = (
        "isin: mask dtype int8, want bool",)
    v = []
    _verifier_with_ops(ops)._check_operator_contracts(v)
    assert v == ["operator-contracts: fake: isin: mask dtype int8, "
                 "want bool"]


def test_delta_epoch_staleness():
    store = types.SimpleNamespace(compaction_epoch=6)
    verifier = PlanVerifier(ldbc_schema(), store=store)
    node = ExpandChainNode.__new__(ExpandChainNode)
    node.__dict__["steps"] = ()
    node.__dict__["child"] = None
    node.__dict__["_chain_spec"] = ((id(store), 5, "jax"), None)
    v = []
    verifier._check_delta_epochs(node, v)
    assert v and "delta-epoch" in v[0] and "epoch 5" in v[0]
    # same memo at the live epoch: clean
    node.__dict__["_chain_spec"] = ((id(store), 6, "jax"), None)
    v2 = []
    verifier._check_delta_epochs(node, v2)
    assert not v2


def test_dtype_contracts_clean_on_real_backends(small_ldbc):
    from repro.core.physical_spec import dtype_contract_failures, get_spec
    for name in ("numpy", "jax"):
        ops = get_spec(name).operators(small_ldbc)
        assert dtype_contract_failures(ops) == [], name


# --------------------------------------------------------------------------
# Appendix-A parity: verify="always" is clean on every query x backend
# --------------------------------------------------------------------------

APPENDIX_A = (
    [(k, q, None) for k, q in Q.QT.items()]
    + [(k, q, Q.QR_PARAMS.get(k)) for k, q in Q.QR.items()]
    + [(k, q, None) for k, q in Q.QC.items()]
    + [(k, q, Q.QIC_PARAMS[k]) for k, q in Q.QIC.items()]
    + [("money_mule", Q.MONEY_MULE, MULE_PARAMS)]
)


@pytest.mark.parametrize("backend", ["numpy", "jax", "sharded"])
def test_appendix_a_verify_parity(small_ldbc, backend):
    g = GOpt(small_ldbc, build_glogue=False, backend=backend)
    for name, text, params in APPENDIX_A:
        rep = g.prepare(text, params, verify="always").explain()
        assert rep.verify is not None, (backend, name)
        assert rep.verify["status"] in (OK, VERIFIED_EMPTY), \
            (backend, name, rep.verify)
        assert rep.verify["violations"] == [], (backend, name)


# --------------------------------------------------------------------------
# Contract linter: the repo itself is clean, and the rules do fire
# --------------------------------------------------------------------------


def test_lint_contracts_repo_clean():
    import pathlib
    import subprocess
    import sys
    repo = pathlib.Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, str(repo / "tools" / "lint_contracts.py"),
         "--strict"], capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 violation(s)" in out.stdout


def test_lint_contracts_flags_a_bare_sync():
    import ast
    import importlib.util
    import pathlib
    path = (pathlib.Path(__file__).resolve().parent.parent / "tools"
            / "lint_contracts.py")
    spec = importlib.util.spec_from_file_location("lint_contracts", path)
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    src = (
        "class JaxOperators:\n"
        "    def expand(self, csr, rows):\n"
        "        total0, _ = self._jaxops.csr_expand_total(csr, rows)\n"
        "        total = int(total0)\n"
        "        cnt = int(self._sync('nonzero', rows.sum()))\n"
        "        hi = int(rows.max())\n"
        "        return total, cnt, hi\n")
    hits = lint.sync_violations(ast.parse(src), "jax_backend.py")
    # the bare scalar read and the bare reduction; not the _sync'd one
    assert [line for line, _ in hits] == [4, 6], hits
    assert all(msg.startswith("R2 device->host sync") for _, msg in hits)
