#!/usr/bin/env python
"""AST-based repo contract lints (DESIGN.md §12.4).

The ``PlanVerifier`` checks *plans*; this tool checks the *code* for the
cross-cutting conventions the verifier's contracts depend on.  Three rules:

R1  host-array discipline — the device backends' data plane
    (``jax_backend.py``, ``sharded_backend.py``, ``jaxops.py``) must not
    materialize host arrays (``np.asarray``, ``np.concatenate``, ...) or
    call ``.to_host`` outside a small allowlist of staging/transfer
    functions.  A stray ``np.*`` in an operator is a silent device->host
    sync that the transfer ledger never sees.

R2  ledger discipline — any function in a compiled backend that calls
    ``jit(`` must record on ``kernel_stats`` (compiles must be visible in
    PROFILE), and the named transfer entry points (``asarray``,
    ``_array_to_host``, ``_upload``, ``to_host``) must record on
    ``transfer_stats``.  Every device->host scalar sync goes through
    ``_sync`` (which records ``sync:<label>`` and opens its span): a line
    marked ``# control-plane sync`` must call it, and ``int(``/``float(``/
    ``bool(``/``np.asarray(`` may not take a device value the rule can
    see — a reduction (``.sum()``, ``.max()``, ...) of anything but
    ``_sync``'s host result, or a name bound from a device call (a
    ``jnp``/``jaxops`` function or a compiled program) — anywhere else.

R3  lock discipline — in ``graphdb/serve.py``, every admission-side call
    (``self.gopt.prepare(``, ``self.gopt.touch_plan(``) must sit lexically
    inside a ``with self._lock`` block, and worker-side methods (run on
    the wave path, outside the lock) must never touch admission-side
    mutable state (``self._queues`` / ``self._pending`` / ``self._rid``).

R4  containment discipline — in the serving path (``graphdb/serve.py``,
    ``graphdb/engine.py``), a function with a broad handler (``except
    Exception`` or bare ``except:``) must route the failure somewhere
    observable: terminal request accounting (``_mark_failed`` /
    ``_fail_crashed``), a stats/ledger attribute, or a recorded fallback.
    A broad handler that silently swallows (the pre-containment
    ``except Exception: continue``) leaves requests in limbo and failures
    invisible to EXPLAIN.

Exit status: 0 when clean; with ``--strict``, 1 on any violation (the CI
gate).  Violations print as ``path:line: R<n> message``.
"""
from __future__ import annotations

import argparse
import ast
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
SRC = REPO / "src" / "repro"

# ------------------------------------------------------------------ R1 config
# device data-plane modules: everything here runs per-operator, per-wave
DATA_PLANE = ("graphdb/jax_backend.py", "graphdb/sharded_backend.py",
              "graphdb/jaxops.py")

# np.<name> calls that materialize / force a host array.  Metadata-only
# helpers (np.iinfo, np.dtype, np.int32-as-dtype) are deliberately absent.
HOST_ARRAY_CALLS = frozenset({
    "asarray", "array", "ascontiguousarray", "frombuffer", "copy",
    "zeros", "ones", "empty", "full", "arange", "repeat", "tile",
    "concatenate", "stack", "hstack", "vstack", "pad",
    "unique", "sort", "argsort", "nonzero", "flatnonzero", "where",
    "searchsorted", "isin", "in1d", "intersect1d", "union1d",
    "cumsum", "bincount", "take", "add",
})

# functions allowed to touch host arrays: the staging/transfer boundary
# (they exist to move data and record it on transfer_stats) plus the fused
# chain's control-plane capacity probe, which is a documented sync point
R1_ALLOW = frozenset({
    "jax_backend.py:FusedChain.run",             # capacity probe (sync point)
    "jax_backend.py:JaxOperators.asarray",       # h2d entry, records ledger
    "jax_backend.py:JaxOperators._array_to_host",  # d2h exit, records ledger
    "jax_backend.py:JaxOperators._upload",       # structure upload, records
    "jax_backend.py:JaxOperators.isin",          # value-list staging via
                                                 # self.asarray (recorded)
    "jax_backend.py:JaxOperators._col_dev",      # one-time column staging
    "jax_backend.py:JaxOperators._vprop_dev",    # one-time property staging
    "jax_backend.py:JaxOperators._eprop_dev",    # one-time property staging
    "sharded_backend.py:ShardedOperators.__init__",  # mesh construction
})

# ------------------------------------------------------------------ R2 config
COMPILED_BACKENDS = ("graphdb/jax_backend.py", "graphdb/sharded_backend.py")
TRANSFER_ENTRY_POINTS = frozenset({"asarray", "to_host", "_array_to_host",
                                   "_upload"})
R2_ALLOW = frozenset({
    # _smap only builds the jitted callable; its callers go through _prog,
    # which records compile:<kind> on first build of each keyed program
    "sharded_backend.py:ShardedOperators._smap",
})
SYNC_MARK = "# control-plane sync"
HOST_CASTS = frozenset({"int", "float", "bool"})
REDUCTIONS = frozenset({"sum", "max", "min", "any", "all", "item"})
# device value roots: calls through these produce device arrays
DEVICE_ROOTS = frozenset({"jnp", "_jnp", "_jaxops", "jaxops", "lax", "_lax"})
# the names the backends bind their compiled programs to before calling
PROGRAM_NAMES = frozenset({"fn", "prog"})
# functions whose reductions read host arrays only
SYNC_ALLOW = frozenset({
    "jax_backend.py:JaxOperators._csr_max_degree",  # host CSR indptr
})

# ------------------------------------------------------------------ R3 config
SERVE = "graphdb/serve.py"
LOCKED_CALLS = ("prepare", "touch_plan")       # self.gopt.<name>( sites
ADMISSION_STATE = frozenset({"_queues", "_pending", "_rid"})
# worker-side methods: run on the wave path, must not reach admission state
WORKER_METHODS = frozenset({"_run_wave", "_run_write_wave", "_update_hotness",
                            "_set_pinned", "_chain_specs", "_exec_group",
                            "_contained_exec", "_level_kw", "_mark_deadline",
                            "_mark_failed", "_breaker", "_breaker_pick",
                            "_breaker_report"})

# ------------------------------------------------------------------ R4 config
CONTAINMENT_FILES = ("graphdb/serve.py", "graphdb/engine.py")
# attributes/calls that make a broad handler's failure observable
R4_SINKS = frozenset({"stats", "fault_stats", "transfer_stats",
                      "kernel_stats", "fallbacks", "record",
                      "_mark_failed", "_fail_crashed", "_mark_deadline",
                      "_contained_exec"})


def _qualname(stack: list[str]) -> str:
    return ".".join(stack) if stack else "<module>"


def _iter_funcs(tree: ast.AST):
    """Yield ``(qualname_stack, node)`` for every function/class scope."""
    def rec(node, stack):
        yield stack, node
        for ch in ast.iter_child_nodes(node):
            if isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                yield from rec(ch, stack + [ch.name])
    yield from rec(tree, [])


def _own_statements(scope: ast.AST):
    """Walk a scope's body without descending into nested def/class scopes."""
    todo = list(ast.iter_child_nodes(scope))
    while todo:
        n = todo.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            continue
        yield n
        todo.extend(ast.iter_child_nodes(n))


def _is_self_attr(node: ast.AST, names) -> str | None:
    if (isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name)
            and node.value.id == "self" and node.attr in names):
        return node.attr
    return None


# --------------------------------------------------------------------------
# R1: no host-array materialization in device data-plane modules
# --------------------------------------------------------------------------

def check_host_arrays(violations: list):
    for rel in DATA_PLANE:
        path = SRC / rel
        tree = ast.parse(path.read_text())
        fname = path.name
        for stack, scope in _iter_funcs(tree):
            qual = f"{fname}:{_qualname(stack)}"
            allowed = qual in R1_ALLOW
            for n in _own_statements(scope):
                if not isinstance(n, ast.Call):
                    continue
                f = n.func
                hit = None
                if (isinstance(f, ast.Attribute)
                        and isinstance(f.value, ast.Name)
                        and f.value.id == "np"
                        and f.attr in HOST_ARRAY_CALLS):
                    hit = f"np.{f.attr}"
                elif isinstance(f, ast.Attribute) and f.attr == "to_host":
                    hit = ".to_host"
                if hit and not allowed:
                    violations.append(
                        (rel, n.lineno,
                         f"R1 host-array call {hit} in data-plane function "
                         f"{_qualname(stack)!r} (not in allowlist — either "
                         f"keep the operator on device or move the staging "
                         f"into a recorded transfer helper)"))


# --------------------------------------------------------------------------
# R2: ledger-recording discipline in compiled backends
# --------------------------------------------------------------------------

def _references_attr(scope, attr: str) -> bool:
    return any(isinstance(n, ast.Attribute) and n.attr == attr
               for n in _own_statements(scope))


def check_ledgers(violations: list):
    for rel in COMPILED_BACKENDS:
        path = SRC / rel
        tree = ast.parse(path.read_text())
        fname = path.name
        for stack, scope in _iter_funcs(tree):
            if not isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            qual = f"{fname}:{_qualname(stack)}"
            calls_jit = any(
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "jit"
                for n in _own_statements(scope))
            if (calls_jit and qual not in R2_ALLOW
                    and not _references_attr(scope, "kernel_stats")):
                violations.append(
                    (rel, scope.lineno,
                     f"R2 {_qualname(stack)!r} calls jit() without "
                     f"recording on kernel_stats (compiles must be visible "
                     f"in PROFILE's kernel ledger)"))
            if (scope.name in TRANSFER_ENTRY_POINTS
                    and not _references_attr(scope, "transfer_stats")):
                violations.append(
                    (rel, scope.lineno,
                     f"R2 transfer entry point {_qualname(stack)!r} never "
                     f"records on transfer_stats"))


def _attr_root(node: ast.AST) -> str | None:
    while isinstance(node, (ast.Attribute, ast.Subscript, ast.Call)):
        node = node.func if isinstance(node, ast.Call) else node.value
        if isinstance(node, ast.Attribute) and node.attr in DEVICE_ROOTS:
            return node.attr
    return node.id if isinstance(node, ast.Name) else None


def _device_call(call: ast.Call) -> bool:
    """A call that returns device values: a ``jnp``/``jaxops``/``lax``
    function, a compiled program (``fn(...)``, ``prog(...)``), or a
    program built and called at once (``self._prog(...)(...)``)."""
    f = call.func
    if isinstance(f, ast.Call):
        return True
    if isinstance(f, ast.Name):
        return f.id in PROGRAM_NAMES
    return _attr_root(f) in DEVICE_ROOTS


def _targets(node: ast.AST) -> list:
    if isinstance(node, ast.Name):
        return [node.id]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [n for e in node.elts for n in _targets(e)]
    return []


def sync_violations(tree: ast.AST, fname: str) -> list:
    """``(line, message)`` for every device->host scalar conversion the
    rule can see outside ``_sync`` in one compiled-backend module."""
    out = []
    for stack, scope in _iter_funcs(tree):
        if not isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        qual = f"{fname}:{_qualname(stack)}"
        if qual in SYNC_ALLOW:
            continue
        nodes = sorted((n for n in _own_statements(scope)
                        if isinstance(n, (ast.Assign, ast.Call))),
                       key=lambda n: (n.lineno, n.col_offset))
        device, host = set(), set()
        for n in nodes:
            if isinstance(n, ast.Assign):
                names = {t for tg in n.targets for t in _targets(tg)}
                v = n.value
                if not isinstance(v, ast.Call):
                    continue
                if isinstance(v.func, ast.Attribute) \
                        and v.func.attr == "_sync":
                    host |= names
                    device -= names
                elif _device_call(v):
                    device |= names
                    host -= names
                continue
            f = n.func
            cast = (isinstance(f, ast.Name) and f.id in HOST_CASTS) or (
                isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
                and f.value.id == "np" and f.attr in ("asarray", "array"))
            if not cast or not n.args:
                continue
            arg = n.args[0]
            hit = None
            if _attr_root(arg) in device:
                hit = "a device value"
            elif (isinstance(arg, ast.Call)
                  and isinstance(arg.func, ast.Attribute)
                  and arg.func.attr in REDUCTIONS
                  and _attr_root(arg.func.value) not in host):
                hit = f"a .{arg.func.attr}() reduction"
            if hit:
                out.append((n.lineno,
                            f"R2 device->host sync ({hit}) in "
                            f"{_qualname(stack)!r} outside _sync (the "
                            f"sync:<label> count and its span miss it)"))
    return out


def check_syncs(violations: list):
    for rel in COMPILED_BACKENDS:
        path = SRC / rel
        text = path.read_text()
        for i, line in enumerate(text.splitlines(), 1):
            if SYNC_MARK in line and "_sync(" not in line:
                violations.append((rel, i, f"R2 line marked "
                                           f"{SYNC_MARK!r} does not call "
                                           f"_sync"))
        for line, msg in sync_violations(ast.parse(text), path.name):
            violations.append((rel, line, msg))


# --------------------------------------------------------------------------
# R3: lock discipline in graphdb/serve.py
# --------------------------------------------------------------------------

def _is_lock_with(node: ast.With) -> bool:
    return any(_is_self_attr(item.context_expr, {"_lock"})
               for item in node.items)


def check_serve_locks(violations: list):
    path = SRC / SERVE
    tree = ast.parse(path.read_text())

    def visit(node, in_lock: bool, method: str | None):
        for ch in ast.iter_child_nodes(node):
            if isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # a nested def is a new execution context: the enclosing
                # `with self._lock` does not guard its (deferred) body
                visit(ch, False, ch.name if method is None else method)
                continue
            if isinstance(ch, ast.ClassDef):
                visit(ch, False, None)
                continue
            locked = in_lock or (isinstance(ch, ast.With)
                                 and _is_lock_with(ch))
            if isinstance(ch, ast.Call):
                f = ch.func
                if (isinstance(f, ast.Attribute) and f.attr in LOCKED_CALLS
                        and _is_self_attr(f.value, {"gopt"}) and not in_lock):
                    violations.append(
                        (SERVE, ch.lineno,
                         f"R3 self.gopt.{f.attr}() outside `with "
                         f"self._lock` (plan-cache admission must be "
                         f"serialized against the worker's touch path)"))
            if (method in WORKER_METHODS
                    and (attr := _is_self_attr(ch, ADMISSION_STATE))):
                violations.append(
                    (SERVE, ch.lineno,
                     f"R3 worker-side method {method!r} touches "
                     f"admission-side state self.{attr}"))
            visit(ch, locked, method)

    visit(tree, False, None)


# --------------------------------------------------------------------------
# R4: broad handlers in the serving path must route failures observably
# --------------------------------------------------------------------------

def _is_broad_handler(h: ast.ExceptHandler) -> bool:
    if h.type is None:
        return True                                       # bare except:
    names = []
    t = h.type
    for n in (t.elts if isinstance(t, ast.Tuple) else [t]):
        if isinstance(n, ast.Name):
            names.append(n.id)
        elif isinstance(n, ast.Attribute):
            names.append(n.attr)
    return any(n in ("Exception", "BaseException") for n in names)


def check_containment(violations: list):
    for rel in CONTAINMENT_FILES:
        path = SRC / rel
        tree = ast.parse(path.read_text())
        for stack, scope in _iter_funcs(tree):
            if not isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            handlers = [h for n in _own_statements(scope)
                        if isinstance(n, ast.Try)
                        for h in n.handlers if _is_broad_handler(h)]
            if not handlers:
                continue
            sinks = any(isinstance(n, ast.Attribute) and n.attr in R4_SINKS
                        for n in _own_statements(scope))
            reraises = any(isinstance(n, ast.Raise)
                           for h in handlers for n in ast.walk(h))
            if not sinks and not reraises:
                violations.append(
                    (rel, handlers[0].lineno,
                     f"R4 {_qualname(stack)!r} catches broad exceptions "
                     f"without recording the failure (must mark requests "
                     f"failed, record on a stats ledger, or re-raise — "
                     f"silent swallows leave requests in limbo)"))


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 on any violation (CI gate)")
    args = ap.parse_args(argv)

    violations: list[tuple[str, int, str]] = []
    check_host_arrays(violations)
    check_ledgers(violations)
    check_syncs(violations)
    check_serve_locks(violations)
    check_containment(violations)

    for rel, line, msg in sorted(violations):
        print(f"src/repro/{rel}:{line}: {msg}")
    n_files = (len(DATA_PLANE) + len(COMPILED_BACKENDS) + 1
               + len(CONTAINMENT_FILES))
    print(f"lint_contracts: {len(violations)} violation(s) across "
          f"{n_files} checked module(s)")
    return 1 if (args.strict and violations) else 0


if __name__ == "__main__":
    sys.exit(main())
