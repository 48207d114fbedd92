"""JAX backend — device-resident binding tables + jit'd padded-block kernels.

Registers the ``"jax"`` PhysicalSpec. OperatorSet v2 (DESIGN.md §7): every
operator takes and returns ``jax.Array`` columns, so the engine's binding
table stays on device across *all* plan steps — pattern loop and relational
tail alike — and crosses to the host exactly once, at result delivery
(``to_host``). ``transfer_stats`` records each host<->device data movement;
the residency tests assert zero ``d2h`` events outside the delivery phase.

- ``expand``    -> ``jaxops.expand_padded``: [R, D_max] neighbor block +
  validity mask, compacted to flat rows on device.
- ``intersect`` -> the ``wcoj_intersect`` Pallas kernel (vectorized
  compare-scan over a padded-ELL adjacency tile; interpret mode on CPU,
  compiled on TPU) for row degrees up to ``MAX_ELL_DEGREE``; beyond that the
  jit'd ``jaxops.bounded_binary_search`` probes the CSR directly.
- relational tail on device: ``join`` is a sort-merge join (stable argsort +
  searchsorted), ``group_reduce`` rides ``jax.ops.segment_*``, and
  ``combine_keys`` packs tuples into dense lexicographic ranks
  (``jaxops.lex_ranks``) — rank order matches the numpy backend's packed-key
  order, so group/join row order stays row-identical across backends.

- ``chain_program`` -> ``FusedChain``: every ``ExpandChainNode`` compiles
  into ONE jit program (``jaxops.build_fused_chain``) — a single device
  dispatch per chain, with pow2 shape-bucketed capacities bounding the
  compile cache and the ``KernelStats`` ledger counter-proving the
  dispatch contract (DESIGN.md §8).

Shapes must be static under jit.  The intersect path pads row blocks to
powers of two (compile count logarithmic in table size), fused chains
bucket their input and per-hop capacities the same way, and the compound
tail kernels (join / group_reduce / combine_keys) pad their inputs to
pow2 capacity buckets too (``jaxops.*_padded``; pad rows are ordered by
an explicit pad flag, never a sentinel value) — so jittered serving-wave
sizes re-hit one compiled program per bucket, counter-proved by the
``compile:join`` / ``compile:group`` / ``compile:lex_ranks``
``KernelStats`` events recorded on first sighting of each bucket key.
Vertex ids, CSR offsets and property columns
stage through int32 (guarded at construction); ``to_host`` widens back to
int64 and canonicalizes the missing-property sentinel.  Control-plane
scalar syncs (row counts, blow-up guards) are not data transfers: each
goes through ``JaxOperators._sync``, which records a ``sync`` event in
``KernelStats`` and opens a ``gopt.sync.<label>`` span.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.pattern import BOTH
from repro.core.physical import (ChainStep, ExpandChainNode, ExpandNode,
                                 JoinNode, PlanNode,
                                 chain_fusable_predicates)
from repro.core.physical_spec import (CostParams, OperatorSet, PhysicalSpec,
                                      register_spec)

# degree ceiling for the padded-ELL kernel layout (DESIGN.md §3: the VPU
# compare-scan beats log-step gathers only while a row block fits in VMEM)
MAX_ELL_DEGREE = 1024
_MIN_BLOCK_ROWS = 8
# rows per device slab: padded blocks are [D_max, slab]; slabbing bounds
# the padded footprint and lets D_max adapt to each slab's real degree skew
_SLAB_ROWS = 1 << 15
# element budget for one [rows, D_max] padded expand block.  The v2 expand
# is a flat repeat-based CSR gather (no padded block, footprint == exact
# output rows, capped by max_out), so this only governs the jit/TPU padded
# variant (``jaxops.expand_padded``)
_EXPAND_ELEMS = 1 << 25

_I32_MIN = np.iinfo(np.int32).min
_I32_MAX = np.iinfo(np.int32).max
_I64_MIN = np.iinfo(np.int64).min

# fused-chain bucketing (DESIGN.md §8): frontier sizes and per-hop
# capacities round up to powers of two with this floor, so the compile
# cache is logarithmic in the size range a chain shape ever sees
_CHAIN_MIN_BUCKET = 8
_CHAIN_PROGRAMS_PER_SHAPE = 4     # bucketed jit programs kept per chain
_CHAIN_SHAPES = 64                # chain handles kept per operator set
# under CPU interpret, fusion pays off while chains are *dispatch-bound*;
# once a hop's capacity grows past this, the pow2 padding + final-argsort
# work of the fused program outweighs the saved launches and the per-hop
# loop is faster (BENCH_fusion.json: ic5 at 2^17 wins fused 3.6x, ic6 at
# 2^18 loses) — volume-bound chains stay on the loop.  On a real
# accelerator one large launch still wins, so the cutoff is interpret-only.
_CHAIN_VOLUME_CUTOFF = 1 << 17

# capacity-bucket floor for the compound relational-tail kernels (the tail
# twin of _CHAIN_MIN_BUCKET): join/group/combine inputs pad up to pow2 so
# the per-kernel compile count is logarithmic in the size range seen
_TAIL_MIN_BUCKET = 16


def _pow2(n: int, floor: int = 1) -> int:
    return max(floor, 1 << max(int(n) - 1, 0).bit_length())


def _pow2_floor(n: int) -> int:
    return 1 << (max(int(n), 1).bit_length() - 1)


class FusedChain:
    """One chain shape's fused-program handle (OperatorSet.chain_program).

    Lifecycle: the engine's first execution of the chain runs the per-hop
    loop and reports the observed per-hop expansion totals via
    ``observe()``; that fixes the pow2 capacity schedule (``caps``), and
    every later execution compiles/reuses ONE jit program per (caps,
    input-bucket, IN-set buckets) key and dispatches the whole chain in a
    single launch.  Capacities only grow (element-wise pow2 max), so the
    compile count for one shape is bounded by the log of the largest size
    it ever sees; an execution whose true totals overflow the current caps
    returns ``None`` (the engine re-runs that one through the loop) and
    regrows the schedule for the next execution."""

    def __init__(self, ops: "JaxOperators", spec):
        self.ops = ops
        self.spec = spec
        self.caps: tuple | None = None
        self._progs: dict = {}    # (caps, in_bucket, value_buckets) -> entry
        # pinned handles survive the operator set's chain-LRU eviction
        # (QueryServer hotness protection, DESIGN.md §9)
        self.pinned = False

    def ready(self) -> bool:
        if self.caps is None:
            return False
        return not (self.ops._interpret
                    and max(self.caps) > _CHAIN_VOLUME_CUTOFF)

    def observe(self, sizes):
        caps = tuple(_pow2(max(int(s), 1), _CHAIN_MIN_BUCKET) for s in sizes)
        if self.caps is not None and len(self.caps) == len(caps):
            caps = tuple(max(a, b) for a, b in zip(self.caps, caps))
        self.caps = caps

    # ------------------------------------------------------------ marshaling
    def _build_desc(self, caps):
        """Static program description for ``jaxops.build_fused_chain`` +
        the ordered property-column requirements."""
        spec = self.spec
        vprops: list[str] = []
        eprops: list[str] = []

        def ref(r):
            if r[0] == "vprop":
                if r[2] not in vprops:
                    vprops.append(r[2])
                return ("vprop", r[1], vprops.index(r[2]))
            if r[0] == "eprop":
                if r[2] not in eprops:
                    eprops.append(r[2])
                return ("eprop", r[1], eprops.index(r[2]))
            return r

        s_map: dict[int, int] = {}
        v_map: dict[int, int] = {}
        for i, s in enumerate(spec.slots):
            if s[0] == "scalar":
                s_map[i] = len(s_map)
            else:
                v_map[i] = len(v_map)

        def sig(p):
            if p is None:
                return None
            if p[0] == "cmp":
                return ("cmp", p[1], ref(p[2]), s_map[p[3]])
            if p[0] == "in":
                return ("in", ref(p[1]), v_map[p[2]])
            return (p[0], tuple(sig(s) for s in p[1]))

        hops = []
        for k, h in enumerate(spec.hops):
            orients = tuple((o.lo, o.hi, o.tidx, o.csr.pos is not None)
                            for o in h.orients)
            probes = []
            for p in h.probes:
                d_hi = self.ops._csr_max_degree(p.orient.csr)
                d_max = _pow2(max(d_hi, 1))
                # Pallas ELL tiles on compiled backends (and for tiny
                # shapes under interpret, to keep the path tested on CPU);
                # per-row bounded binary search otherwise
                ell = (d_hi > 0 and d_hi <= MAX_ELL_DEGREE
                       and (not self.ops._interpret
                            or (d_max <= 64 and caps[k] <= 4096)))
                probes.append((p.from_alias, p.edge_alias, p.orient.lo,
                               p.orient.hi, p.vlo, p.vhi,
                               p.orient.tidx, p.orient.csr.pos is not None,
                               "ell" if ell else "bsearch", d_max))
            hops.append((h.from_alias, h.alias, h.edge_alias, orients,
                         tuple(probes), sig(h.pred_sig)))
        return (spec.source, tuple(hops)), tuple(vprops), tuple(eprops)

    def _csr_args(self, o):
        indptr, indices, pos = self.ops._csr_dev(o.csr)
        return (indptr, indices, pos if pos is not None else indices)

    # -------------------------------------------------------------- dispatch
    def run(self, src, nrows, scalars, value_lists, max_rows):
        """One fused dispatch; returns ``(rows, cols, n)`` with exact-size
        device columns, or ``None`` after a capacity overflow (caps regrow;
        the caller falls back to the per-hop loop for this execution)."""
        ops = self.ops
        jnp = ops._jnp
        n = int(nrows)
        in_bucket = _pow2(n, _CHAIN_MIN_BUCKET)
        vb = tuple(_pow2(max(len(v), 1)) for v in value_lists)
        # a runtime-empty IN-set is a *static* program variant (matches
        # nothing even under NOT/OR), part of the bucketed cache key
        empties = tuple(i for i, v in enumerate(value_lists) if len(v) == 0)
        key = (self.caps, in_bucket, vb, empties)
        entry = self._progs.get(key)
        if entry is not None:
            self._progs[key] = self._progs.pop(key)   # LRU touch
        else:
            from repro.graphdb import jaxops
            desc, vprops, eprops = self._build_desc(self.caps)
            fn = ops._jax.jit(jaxops.build_fused_chain(
                desc, self.caps, in_bucket, ops._interpret,
                empty_values=empties))
            n_ell = sum(pr[8] == "ell" for h in desc[1] for pr in h[4])
            entry = (fn, vprops, eprops, n_ell)
            if len(self._progs) >= _CHAIN_PROGRAMS_PER_SHAPE:
                self._progs.pop(next(iter(self._progs)))
            self._progs[key] = entry
            ops.kernel_stats.record("compile", "fused_chain")
        fn, vprops, eprops, n_ell = entry
        src = jnp.asarray(src)
        if in_bucket > n:
            src = jnp.pad(src, (0, in_bucket - n))
        csrs = tuple((tuple(self._csr_args(o) for o in h.orients),
                      tuple(self._csr_args(p.orient) for p in h.probes))
                     for h in self.spec.hops)
        vp = tuple(ops._vprop_dev(p) for p in vprops)
        # base columns only ((offsets, flat) — drop the nnz count): chains
        # decline whenever the snapshot touches their triples, so overlay
        # edge positions never reach a fused program
        ep = tuple(ops._eprop_dev(p)[:2] for p in eprops)
        scal = ops.asarray(np.asarray(list(scalars), dtype=np.int32))
        vals = []
        for v, b in zip(value_lists, vb):
            a = np.asarray(v, dtype=np.int32)
            if a.shape[0] == 0:
                a = np.zeros(b, np.int32)          # dead arg (empty variant)
            elif a.shape[0] < b:                   # duplicate-pad: same set
                a = np.concatenate([a, np.full(b - a.shape[0], a[0],
                                               np.int32)])
            vals.append(ops.asarray(a))
        out, n0, needed, needed_f = fn(src, n, csrs, vp, ep, scal,
                                       tuple(vals))
        ops.kernel_stats.record("dispatch", "fused_chain")
        if n_ell:
            ops.kernel_stats.record("dispatch", "wcoj", n_ell)
        needed_h, nf, n0 = ops._sync("fused_chain", (needed, needed_f, n0))
        if nf.size and float(nf.max()) > _I32_MAX - 256:
            raise RuntimeError(
                f"intermediate blow-up: chain expansion would produce "
                f"~{float(nf.max()):.3g} rows (beyond the int32 staging "
                f"envelope)")
        if (needed_h > max_rows).any():
            raise RuntimeError(
                f"intermediate blow-up: chain expansion would produce "
                f"{int(needed_h.max())} rows > cap {max_rows}")
        if (needed_h > np.asarray(self.caps)).any():
            self.observe(needed_h.tolist())
            return None
        n_out = int(n0)
        rows = out["__rows"][:n_out]
        cols = {k: v[:n_out] for k, v in out.items()
                if k not in ("__rows", self.spec.source)}
        return rows, cols, n_out


class JaxOperators(OperatorSet):
    """Device-resident operator set: columns are ``jax.Array`` int32."""

    name = "jax"
    supports_chains = True
    compiled = True

    def __init__(self, store):
        super().__init__(store)
        import jax  # deferred so the registry import stays light
        import jax.numpy as jnp
        from repro.graphdb import jaxops
        from repro.kernels.wcoj_intersect.ops import wcoj_intersect
        self._jax = jax
        self._jnp = jnp
        self._jaxops = jaxops
        self._wcoj = wcoj_intersect
        self._interpret = jax.default_backend() != "tpu"
        id_space = getattr(store, "id_space", store.n_vertices)
        if max(id_space, store.n_edges) >= _I32_MAX:
            raise ValueError(
                "jax backend stages vertex ids and CSR offsets through "
                f"int32; store has {store.n_vertices} vertices / "
                f"{store.n_edges} edges")
        self._dev = {}    # id(csr) -> (indptr_dev, indices_dev, pos_dev|None)
        self._props = {}  # ("v"|"e", prop, epoch) -> device property column(s)
        self._cols = {}   # id(host col) -> (host col ref, device twin)
        self._chains = {}     # (chain signature, csr ids) -> FusedChain
        self._max_deg = {}    # id(csr) -> int global max degree
        # tail-kernel bucket keys already traced: mirrors the module-level
        # jit caches so KernelStats can record one compile per bucket
        self._tail_shapes: set = set()

    # ---------------------------------------------------------- fused chains
    @staticmethod
    def _chain_key(spec):
        return (spec.signature(),
                tuple(id(o.csr) for h in spec.hops
                      for o in list(h.orients) + [p.orient
                                                  for p in h.probes]))

    def chain_program(self, spec) -> FusedChain:
        key = self._chain_key(spec)
        prog = self._chains.get(key)
        if prog is not None:
            self._chains[key] = self._chains.pop(key)   # LRU touch
        else:
            if len(self._chains) >= _CHAIN_SHAPES:
                victim = next((k for k, v in self._chains.items()
                               if not v.pinned), None)
                # all pinned: evict the coldest anyway (capacity wins)
                self._chains.pop(victim if victim is not None
                                 else next(iter(self._chains)))
            prog = self._chains[key] = FusedChain(self, spec)
        return prog

    def pin_chain(self, spec, pinned: bool = True) -> bool:
        """Protect (or release) an existing chain handle — with its bucketed
        compiled programs — from chain-LRU eviction.  Only handles that
        already exist are pinned: a plan with no executed chain has nothing
        worth protecting."""
        prog = self._chains.get(self._chain_key(spec))
        if prog is None:
            return False
        prog.pinned = bool(pinned)
        return True

    def _tail_compile(self, kind: str, key: tuple):
        """Record ``compile:<kind>`` on the first sighting of a bucketed
        tail-kernel shape key (mirroring the jit cache, which is keyed by
        exactly these padded shapes)."""
        if (kind, key) not in self._tail_shapes:
            self._tail_shapes.add((kind, key))
            self.kernel_stats.record("compile", kind)

    def _csr_max_degree(self, csr) -> int:
        d = self._max_deg.get(id(csr))
        if d is None:
            deg = csr.indptr[1:] - csr.indptr[:-1]
            d = self._max_deg[id(csr)] = int(deg.max()) if deg.size else 0
        return d

    def block_ready(self, arrays):
        return self._jax.block_until_ready(arrays)

    def span(self, name: str, **args):
        return self._jax.profiler.TraceAnnotation(name, **args)

    def _sync(self, label: str, x):
        """The one device->host round trip of control-plane scalars (row
        counts, blow-up guards): ``x`` is a device value or a tuple of
        them, fetched together as host numpy.  Records ``sync:<label>`` in
        ``KernelStats`` and opens a ``gopt.sync.<label>`` span, so the
        count and the trace name every place the host waits."""
        self.kernel_stats.record("sync", label)
        with self.span("gopt.sync." + label):
            return self._jax.device_get(x)

    # ------------------------------------------------------------ transfers
    def asarray(self, values):
        if isinstance(values, self._jax.Array):
            return values
        a = np.asarray(values)
        self.transfer_stats.record("h2d", a.size)
        return self._jnp.asarray(a)

    def _array_to_host(self, a) -> np.ndarray:
        if not isinstance(a, self._jax.Array):
            return np.asarray(a)
        self.transfer_stats.record("d2h", a.size)
        with self.span("gopt.d2h"):
            h = np.asarray(a)
        if h.dtype == np.int32:
            h64 = h.astype(np.int64)
            h64[h64 == _I32_MIN] = _I64_MIN   # missing-prop sentinel widens
            return h64
        if h.dtype == np.float32:
            return h.astype(np.float64)
        return h

    def _upload(self, a: np.ndarray):
        """Graph-structure/property upload (cached by callers): int32 on
        device, recorded as h2d."""
        if a.dtype.kind == "i" and a.size and (
                a.max() > _I32_MAX or a.min() < _I32_MIN):
            raise ValueError("column exceeds the jax backend's int32 "
                             "staging envelope")
        self.transfer_stats.record("h2d", a.size)
        return self._jnp.asarray(a.astype(np.int32)
                                 if a.dtype.kind == "i" else a)

    # ------------------------------------------------------ array primitives
    def take(self, a, idx):
        # jnp.take(mode="clip") skips the eager advanced-indexing rewrite
        # machinery (~0.5ms of host python per gather); engine indices are
        # in-range by construction
        return self._jnp.take(self._jnp.asarray(a), idx, axis=0, mode="clip")

    def mask(self, a, m):
        return self._jnp.asarray(a)[self._jnp.asarray(m)]

    def concat(self, parts: list):
        if not parts:
            return self._jnp.zeros(0, self._jnp.int32)
        if len(parts) == 1:
            return self._jnp.asarray(parts[0])
        return self._jnp.concatenate([self._jnp.asarray(p) for p in parts])

    def nonzero(self, m):
        jnp = self._jnp
        m = jnp.asarray(m)
        if m.dtype != bool:
            m = m != 0          # int 0/1 masks: sum/argsort need real bools
        cnt = int(self._sync("nonzero", m.sum()))
        if cnt == 0:
            return jnp.zeros(0, jnp.int32)
        return self._true_positions(m, cnt)

    def nonzero_segments(self, m):
        # one sync fetches the k per-segment counts (their sum sizes the
        # slice); the flattened mask's positions split into (segment, row)
        # on device
        jnp = self._jnp
        m = jnp.asarray(m)
        if m.dtype != bool:
            m = m != 0          # int 0/1 masks: the argsort needs real bools
        counts = self._sync("refilter", m.sum(axis=1))
        total = int(counts.sum())
        if total == 0:
            empty = jnp.zeros(0, jnp.int32)
            return empty, empty, counts
        flat = self._true_positions(m.reshape(-1), total)
        n = m.shape[1]
        return flat // n, flat % n, counts

    def _true_positions(self, m, cnt: int):
        # argsort-shaped flatnonzero of a flat bool mask holding ``cnt``
        # Trues: jnp.nonzero's eager path rides heavy python machinery per
        # call.  A stable sort puts True positions first in original
        # order.  The mask pads to a pow2 capacity bucket (pads False, so
        # they sort last among the dropped rows) — mask/compaction sites
        # key compiles on the bucket, not the exact table length.
        np2 = _pow2(m.shape[0], _TAIL_MIN_BUCKET)
        self._tail_compile("nonzero", (np2,))
        self.kernel_stats.record("dispatch", "nonzero")
        order = self._jnp.argsort(~self._pad(m, np2, False))   # stable
        return order[:cnt].astype(self._jnp.int32)

    def full(self, n: int, value):
        return self._jnp.full(n, value)

    def arange(self, n: int):
        return self._jnp.arange(n, dtype=self._jnp.int32)

    def isin(self, a, values):
        vals = np.asarray(list(values), dtype=np.int64)
        # values outside the int32 envelope cannot match any staged column
        vals = vals[(vals <= _I32_MAX) & (vals > _I32_MIN)]
        return self._jnp.isin(self._jnp.asarray(a), self.asarray(vals))

    def searchsorted(self, sorted_arr, values, side: str = "left"):
        return self._jnp.searchsorted(self._jnp.asarray(sorted_arr),
                                      self._jnp.asarray(values), side=side)

    def where(self, cond, a, b):
        return self._jnp.where(self._jnp.asarray(cond),
                               self._jnp.asarray(a), self._jnp.asarray(b))

    def lexsort(self, cols: list):
        return self._jnp.lexsort(tuple(self._jnp.asarray(c) for c in cols))

    def distinct_indices(self, key):
        # pow2-bucketed like the compound tail kernels: pad rows sort last
        # by an explicit pad flag (any key value stays distinct-correct)
        # and never start a counted run
        jnp = self._jnp
        key = jnp.asarray(key)
        n = key.shape[0]
        if n == 0:
            return jnp.zeros(0, jnp.int32)
        np2 = _pow2(n, _TAIL_MIN_BUCKET)
        self._tail_compile("distinct", (np2,))
        self.kernel_stats.record("dispatch", "distinct")
        pf = jnp.arange(np2) >= n
        kp = self._pad(key, np2)
        order = jnp.lexsort((kp, pf))              # stable -> minimal index
        sk = self.take(kp, order)
        spf = self.take(pf, order)
        flag = jnp.concatenate([jnp.ones(1, bool),
                                sk[1:] != sk[:-1]]) & ~spf
        return jnp.sort(self.take(order, self.nonzero(flag)))

    # ------------------------------------------------------ property gathers
    def _col_dev(self, host_col: np.ndarray):
        """Device twin of a host overlay column, keyed by object identity
        (the mutable store retains every column it publishes, so an id is
        stable while the entry is valid; the stored host ref guards against
        address reuse after a gc).  The host INT64_MIN missing sentinel is
        narrowed to the in-band int32 one before staging."""
        key = id(host_col)
        ent = self._cols.get(key)
        if ent is None or ent[0] is not host_col:
            staged = np.where(host_col == _I64_MIN, _I32_MIN, host_col)
            ent = self._cols[key] = (host_col, self._upload(staged))
        return ent[1]

    def _vprop_dev(self, prop: str):
        """One device column per vertex property over the *base* store,
        indexed by *global* id (missing types filled with the int32
        sentinel) — a property gather is then a single device take instead
        of a per-type where-loop.  Keyed by compaction epoch so a rebuilt
        base CSR re-stages."""
        key = ("v", prop, getattr(self.store, "compaction_epoch", 0))
        ent = self._props.get(key)
        if ent is None:
            st = getattr(self.store, "base", self.store)
            # in-band missing sentinel, like the host path's INT64_MIN:
            # only a stored value of exactly INT32_MIN would collide
            col = np.full(st.n_vertices, _I32_MIN, dtype=np.int64)
            for t in st._sorted_types():
                tc = st.v_props.get(t, {}).get(prop)
                if tc is None or tc.shape[0] == 0:
                    continue
                off = st.v_offset[t]
                col[off:off + tc.shape[0]] = tc
            ent = self._props[key] = self._upload(col)
        return ent

    def _eprop_dev(self, prop: str):
        """Per-triple edge-property columns of the *base* store concatenated
        on device, plus the per-triple base offsets:
        ``col[offset[tidx] + pos]``.  The total base nnz rides along so the
        overlay merge can split positions."""
        key = ("e", prop, getattr(self.store, "compaction_epoch", 0))
        ent = self._props.get(key)
        if ent is None:
            st = getattr(self.store, "base", self.store)
            triples = sorted(st.out_csr, key=repr)
            offsets, parts, off = [], [], 0
            for t in triples:
                tc = st.e_props.get(t, {}).get(prop)
                n = st.out_csr[t].nnz
                offsets.append(off)
                part = np.full(n, _I32_MIN, dtype=np.int64)
                if tc is not None and tc.shape[0]:
                    part[:tc.shape[0]] = tc
                parts.append(part)
                off += n
            flat = (np.concatenate(parts) if parts
                    else np.zeros(0, np.int64))
            ent = self._props[key] = (
                self._upload(np.asarray(offsets, dtype=np.int64)),
                self._upload(flat), off)
        return ent

    def vertex_prop(self, ids, prop: str):
        ids = self._jnp.asarray(ids)
        out = self.take(self._vprop_dev(prop), ids)
        st = self.store
        bv = getattr(st, "base_n_vertices", None)
        if bv is not None and getattr(st, "id_space", bv) > bv:
            ext = self._col_dev(st.ext_vertex_prop_column(prop))
            out = self._jnp.where(ids < bv, out, self.take(ext, ids - bv))
        return out

    def edge_prop(self, triple_ids, pos, prop: str):
        jnp = self._jnp
        pos = jnp.asarray(pos)
        offsets, flat, nbase = self._eprop_dev(prop)
        if flat.shape[0] == 0:
            out = jnp.full(pos.shape, _I32_MIN, jnp.int32)
        else:
            # clip-mode take keeps overlay positions (>= nbase) harmless
            # here; the where below overwrites those lanes
            out = self.take(flat, self.take(offsets,
                                            jnp.asarray(triple_ids)) + pos)
        st = self.store
        if getattr(st, "overlay_edge_slots", 0) > 0:
            ov = self._col_dev(st.overlay_edge_prop_column(prop))
            out = jnp.where(pos < nbase, out, self.take(ov, pos - nbase))
        return out

    # --------------------------------------------------------------- pattern
    def _csr_dev(self, csr):
        key = id(csr)
        ent = self._dev.get(key)
        if ent is None:
            ent = (self._upload(csr.indptr), self._upload(csr.indices),
                   self._upload(csr.pos) if csr.pos is not None else None)
            self._dev[key] = ent
        return ent

    def _pad(self, a, n: int, fill=0):
        return self._jnp.pad(a, (0, n - a.shape[0]), constant_values=fill)

    def scan(self, lo: int, hi: int):
        return self._jnp.arange(lo, hi, dtype=self._jnp.int32)

    def expand(self, csr, rows_local, max_out=None):
        """Device twin of ``vecops.expand_csr``: repeat-based flat CSR
        gather (row-major order, exactly the host path's rows).  Sort- and
        scatter-free — on CPU XLA a scatter serializes, and a padded
        [R, D_max] block (``jaxops.expand_padded``, the jit/TPU-shaped
        variant) would cost an extra materialization + compaction pass;
        the flat gather materializes exactly ``total`` rows, which
        ``max_out`` caps *before* any device work."""
        jnp = self._jnp
        rows = jnp.asarray(rows_local)
        R = rows.shape[0]
        z = jnp.zeros(0, jnp.int32)
        if R == 0:
            return z, z, z
        indptr_d, indices_d, pos_d = self._csr_dev(csr)
        total0, approx0 = self._jaxops.csr_expand_total(indptr_d, rows)
        total, approx = self._sync("expand", (total0, approx0))
        total = int(total)
        if float(approx) > _I32_MAX - 256:           # int32 sum wrapped
            raise RuntimeError(f"intermediate blow-up: expansion would "
                               f"produce ~{float(approx):.3g} rows "
                               f"(beyond the int32 staging envelope)")
        if max_out is not None and total > max_out:
            raise RuntimeError(f"intermediate blow-up: expansion would "
                               f"produce {total} rows > cap {max_out}")
        self.kernel_stats.record("dispatch", "expand", 1 + (total > 0))
        if total == 0:
            return z, z, z
        return self._jaxops.csr_expand_flat(
            indptr_d, indices_d,
            pos_d if pos_d is not None else indices_d, rows,
            total=total, has_pos=pos_d is not None)

    # ------------------------------------------------------------- intersect
    def intersect(self, csr, rows_local, targets):
        jnp = self._jnp
        rows = jnp.asarray(rows_local)
        tgt = jnp.asarray(targets)
        R = rows.shape[0]
        if R == 0:
            return jnp.zeros(0, bool), jnp.zeros(0, jnp.int32)
        indptr_d, indices_d, pos_d = self._csr_dev(csr)
        deg = self.take(indptr_d, rows + 1) - self.take(indptr_d, rows)
        founds, fposs = [], []
        for s in range(0, R, _SLAB_ROWS):
            e = min(s + _SLAB_ROWS, R)
            d_hi = int(self._sync("intersect", deg[s:e].max()))
            if d_hi == 0:
                founds.append(jnp.zeros(e - s, bool))
                fposs.append(jnp.zeros(e - s, jnp.int32))
            elif d_hi <= MAX_ELL_DEGREE:
                self.kernel_stats.record("dispatch", "intersect")
                self.kernel_stats.record("dispatch", "wcoj")
                f, p = self._intersect_ell(indptr_d, indices_d, rows[s:e],
                                           tgt[s:e], d_hi)
                founds.append(f)
                fposs.append(p)
            else:
                self.kernel_stats.record("dispatch", "intersect", 1)
                f, p = self._intersect_bsearch(indptr_d, indices_d,
                                               rows[s:e], tgt[s:e])
                founds.append(f)
                fposs.append(p)
        found = founds[0] if len(founds) == 1 else jnp.concatenate(founds)
        # the ELL kernel emits an int 0/1 found column; the operator contract
        # is a bool mask (callers compose it with ~/& — bitwise on ints
        # silently corrupts)
        found = found.astype(bool)
        fpos = fposs[0] if len(fposs) == 1 else jnp.concatenate(fposs)
        mapped = self.take(pos_d, fpos) if pos_d is not None else fpos
        epos = jnp.where(found, mapped, 0)
        return found, epos

    def _intersect_ell(self, indptr_d, indices_d, rows, targets, d_hi):
        """Pallas kernel path: gather padded-ELL rows, compare-scan probe."""
        from repro.kernels.wcoj_intersect.ops import gather_rows
        jnp = self._jnp
        d_max = _pow2(d_hi)
        R = rows.shape[0]
        rp = _pow2(R, _MIN_BLOCK_ROWS)
        rows_p = self._pad(rows, rp)
        # pad targets with -2: never matches a real id (>=0) or ELL pad (-1)
        tgt_p = self._pad(targets, rp, -2)
        adj = gather_rows(indices_d, indptr_d, rows_p, d_max)
        found_d, pos_d = self._wcoj(adj, tgt_p, interpret=self._interpret)
        pos_in_row = pos_d[:R].astype(jnp.int32)
        return found_d[:R], self.take(indptr_d, rows) + pos_in_row

    def _intersect_bsearch(self, indptr_d, indices_d, rows, targets):
        """High-degree fallback: jit'd per-row bounded binary search."""
        jnp = self._jnp
        R = rows.shape[0]
        rp = _pow2(R, _MIN_BLOCK_ROWS)
        lo = self._pad(self.take(indptr_d, rows), rp)
        hi = self._pad(self.take(indptr_d, rows + 1), rp)
        tgt = self._pad(targets, rp, -2)
        found_d, pos_d = self._jaxops.bounded_binary_search(
            indices_d, lo, hi, tgt)
        return found_d[:R], pos_d[:R].astype(jnp.int32)

    # --------------------------------------------------------- relational tail
    # The compound tail kernels pad their inputs to pow2 capacity buckets
    # (pad rows ordered last by an explicit pad flag, exact results sliced
    # to the true counts) so recurring jittered sizes — serving waves —
    # re-hit one compiled program per bucket; _tail_compile counter-proves
    # the plateau.

    def join(self, lkeys, rkeys, max_out=None):
        jnp = self._jnp
        lk = jnp.asarray(lkeys)
        rk = jnp.asarray(rkeys)
        L, R = lk.shape[0], rk.shape[0]
        z = jnp.zeros(0, jnp.int32)
        if L == 0 or R == 0:
            return z, z
        Lp = _pow2(L, _TAIL_MIN_BUCKET)
        Rp = _pow2(R, _TAIL_MIN_BUCKET)
        self._tail_compile("join", (Lp, Rp))
        self.kernel_stats.record("dispatch", "join")
        # INT32_MAX padding keeps the right sorted column non-decreasing
        # for searchsorted; ordering itself rides the pad flag, so real
        # keys equal to the pad value still join correctly
        lorder, rorder, lo, cnt, total0, approx0 = \
            self._jaxops.sortmerge_bounds_padded(
                self._pad(lk, Lp, _I32_MAX), self._pad(rk, Rp, _I32_MAX),
                L, R)
        total, approx = self._sync("join", (total0, approx0))
        total = int(total)
        if float(approx) > _I32_MAX - 256:          # int32 sum wrapped
            raise RuntimeError(f"intermediate blow-up: join would produce "
                               f"~{float(approx):.3g} rows (beyond the "
                               f"int32 staging envelope)")
        if max_out is not None and total > max_out:
            raise RuntimeError(f"intermediate blow-up: join would produce "
                               f"{total} rows > cap {max_out}")
        if total == 0:
            return z, z
        Tp = _pow2(total, _TAIL_MIN_BUCKET)
        self._tail_compile("join_pairs", (Lp, Tp))
        self.kernel_stats.record("dispatch", "join")
        lidx, ridx = self._jaxops.sortmerge_pairs(lorder, rorder, lo, cnt,
                                                  total=Tp)
        return lidx[:total], ridx[:total]

    def combine_keys(self, cols: list):
        jnp = self._jnp
        cols = [jnp.asarray(c) for c in cols]
        if len(cols) == 1:
            return cols[0]
        n = cols[0].shape[0]
        if n == 0:
            return jnp.zeros(0, jnp.int32)
        np2 = _pow2(n, _TAIL_MIN_BUCKET)
        self._tail_compile("lex_ranks", (np2, len(cols)))
        self.kernel_stats.record("dispatch", "lex_ranks")
        ranks = self._jaxops.lex_ranks_padded(
            [self._pad(c, np2) for c in cols], n)
        return ranks[:n]

    def group_reduce(self, keys, values):
        """Sorted-run grouping: one stable sort by key, then every
        aggregate is a cumsum/boundary gather over the sorted runs —
        sort/gather-shaped on purpose (XLA scatter, hence
        ``jax.ops.segment_*``, serializes on CPU).  Groups ascend by key;
        ``first`` is each group's minimal original row (stable sort)."""
        jnp = self._jnp
        keys = jnp.asarray(keys)
        n = keys.shape[0]
        if n == 0:
            z = jnp.zeros(0, jnp.int32)
            return z, {name: z for name in values}
        bad = [fn for fn, _ in values.values()
               if fn not in ("COUNT", "SUM", "AVG", "MIN", "MAX")]
        if bad:
            raise ValueError(f"unknown aggregate {bad[0]}")
        np2 = _pow2(n, _TAIL_MIN_BUCKET)
        self._tail_compile("group", (np2,))
        self.kernel_stats.record("dispatch", "group", 2)
        keys_p = self._pad(keys, np2)
        order, _vstart, flag_order, ng0 = \
            self._jaxops.group_boundaries_padded(keys_p, n)
        ng = int(self._sync("group", ng0))
        starts = flag_order[:ng]                     # ascending run starts
        gp = _pow2(ng, _TAIL_MIN_BUCKET)
        names = list(values)
        cols_p = tuple(self._pad(jnp.asarray(values[nm][1]), np2)
                       for nm in names)
        fns = tuple(values[nm][0] for nm in names)
        self._tail_compile("group_agg",
                           (np2, gp, fns,
                            tuple(str(c.dtype) for c in cols_p)))
        # starts pad with the terminal bound n: dummy trailing groups get
        # count 0 and are sliced off below
        first, outs = self._jaxops.group_aggregate_padded(
            order, self._pad(starts, gp, n), keys_p, n, cols_p, fns)
        return first[:ng], {nm: o[:ng] for nm, o in zip(names, outs)}


def _hop_predicates(pattern, h: ExpandNode) -> list:
    preds = list(pattern.vertices[h.new_alias].predicates or [])
    for e in h.edges:
        preds.extend(e.predicates or [])
    return preds


def fuse_expand_chain(node: PlanNode, ctx) -> PlanNode:
    """Post-CBO physical rewrite (the ``PhysicalSpec.physical_rules`` hook):
    fuse runs of >= 2 consecutive expansions into one ``ExpandChainNode``.

    With device-resident tables (OperatorSet v2) every hop already stays on
    device; chaining pays twice: the thin frontier carries only the hop
    columns through the per-hop gathers, and the backend compiles the whole
    chain into ONE jit program — a single device dispatch instead of one
    per hop (DESIGN.md §8).  A hop fuses when its source alias is carried
    by the chain (or anchors it) and its predicates are chain-fusable
    (``core.physical.chain_fusable_predicates``: comparisons/IN-sets over
    carried aliases against literals or parameters — the folded filter
    still runs *at its own hop* inside the program, so intermediates stay
    bounded); other predicates close the chain, keeping their hop on the
    per-hop path.  A trailing expand-and-intersect whose probe edges read
    carried aliases folds in as the chain's final WCOJ step.  Fusion is
    packaging, not planning: ``ExpandChainNode.unfused()`` recovers the
    exact pre-fusion plan, and results are row-identical."""
    pattern = ctx.pattern()
    fused = False

    def rewrite(n: PlanNode) -> PlanNode:
        if isinstance(n, JoinNode):
            return dataclasses.replace(n, left=rewrite(n.left),
                                       right=rewrite(n.right))
        if not isinstance(n, ExpandNode):
            return n
        run = [n]                       # the maximal expand run, bottom-up
        cur = n.child
        while isinstance(cur, ExpandNode):
            run.append(cur)
            cur = cur.child
        run.reverse()                   # execution order
        out = rewrite(cur)
        pending: list[tuple[ExpandNode, str]] = []

        def flush():
            nonlocal out, fused
            if len(pending) >= 2:
                fused = True
                steps = [ChainStep(h.edges[0], frm, h.new_alias,
                                   h.est_frequency, h.est_cost,
                                   intersect_edges=tuple(h.edges[1:]))
                         for h, frm in pending]
                out = ExpandChainNode(out, steps,
                                      est_frequency=steps[-1].est_frequency,
                                      est_cost=steps[-1].est_cost)
            else:
                for h, frm in pending:
                    out = ExpandNode(out, h.new_alias, h.edges,
                                     est_frequency=h.est_frequency,
                                     est_cost=h.est_cost)
            pending.clear()

        def preds_fusable(h, frm):
            va = ({pending[0][1]} if pending else {frm})
            va |= {x.new_alias for x, _ in pending} | {h.new_alias}
            ea = {x.edges[0].alias for x, _ in pending} | \
                 {e.alias for e in h.edges}
            return chain_fusable_predicates(_hop_predicates(pattern, h),
                                            va, ea)

        for h in run:
            frm = h.edges[0].other(h.new_alias) if h.edges else None
            if len(h.edges) == 1:
                fusable = preds_fusable(h, frm)
                tail = False
            else:
                # expand-and-intersect: fold as the chain's final WCOJ step
                # when every probe edge reads a carried alias and each is a
                # pure filter (one orientation: directional, single triple)
                carried = ({pending[0][1]} | {x.new_alias
                                              for x, _ in pending}
                           if pending else set())
                tail = fusable = bool(pending) and frm in carried and all(
                    e.other(h.new_alias) in carried
                    and e.direction != BOTH and len(e.triples) == 1
                    for e in h.edges[1:]) and preds_fusable(h, frm)
            if fusable and not tail and pending:
                carried = {pending[0][1]} | {x.new_alias for x, _ in pending}
                if frm not in carried:
                    # source bound below the current run (e.g. by a join
                    # child): close this chain and anchor a new one here
                    flush()
                    fusable = preds_fusable(h, frm)
            if fusable:
                pending.append((h, frm))
                if tail:                # the wcoj step ends its chain
                    flush()
            else:
                flush()
                out = ExpandNode(out, h.new_alias, h.edges,
                                 est_frequency=h.est_frequency,
                                 est_cost=h.est_cost)
        flush()
        return out

    out = rewrite(node)
    # no run fused: hand back the input so PhysicalRulesPass (and its
    # trace) correctly records the plan as unchanged
    return out if fused else node


# Calibrated from BENCH_backends.json (sf=0.2 CPU/interpret timings) via
# benchmarks/calibrate_costs.py: expand-dominated chain probes run ~5.3x the
# numpy host path (dispatch + padded-block overhead), while cyclic queries
# whose plans close edges with WCOJ membership probes run ~34x — so the CBO
# should spend joins/expansions to avoid intersections on this backend.
# Scan and the (now device-native) join stay at the numpy baseline.
# Re-derive after re-benchmarking (e.g. on real TPU, where these flip
# dramatically).
JAX_SPEC = register_spec(PhysicalSpec(
    name="jax",
    make_operators=JaxOperators,
    cost=CostParams(alpha_scan=1.0, alpha_expand=5.3,
                    alpha_intersect=34.0, alpha_join=1.0),
    description="device-resident columns; jit'd padded-block primitives + "
                "wcoj_intersect Pallas kernel (interpret on CPU, compiled "
                "on TPU); segment-reduce/sort-merge relational tail",
    physical_rules=(fuse_expand_chain,),
))
