"""Sharded multi-device backend: mesh-partitioned CSR + collective operators.

The third registered ``OperatorSet`` (DESIGN.md §10).  The CSR graph is
vertex-cut partitioned across a JAX device mesh (``graphdb.partition``):
each shard owns a contiguous range of a CSR's keyed rows, so the adjacency
of a frontier vertex is readable only on its owning shard.  Every pattern
operator is a ``shard_map`` program over the mesh's ``data`` axis built
from real collectives:

- **expand** — the frontier's per-row degrees are resolved by each shard
  contributing the rows it owns and combining with ``lax.psum`` (the
  frontier exchange: every shard learns the full degree vector), then each
  shard materializes the neighbor/edge-position values of its owned rows
  at their row-major output offsets and a ``lax.psum_scatter`` both
  combines the per-shard contributions and leaves the output *sharded* —
  each device holds one contiguous chunk of the expansion.
- **intersect** — probes route the same way: owning shards run the bounded
  binary search locally and ``lax.psum`` combines the (owner-unique)
  found/edge-position vectors.
- the **relational tail** (sort-merge join, combine_keys, distinct,
  order/limit keys) gathers its sharded operand columns with explicit
  ``lax.all_gather`` collectives and reuses the jax backend's bucketed
  tail kernels on the gathered replicas, while **group_reduce** runs a
  genuinely distributed two-phase aggregation: per-shard partial
  aggregates over each shard's row chunk, combined across the mesh with
  ``lax.psum`` / ``lax.pmin`` / ``lax.pmax``.

Every collective is recorded in the ``ExchangeStats`` ledger
(``physical_spec``), the third sibling of ``TransferStats``/``KernelStats``
— together they prove the distributed residency contract: frontier
exchanges happen device-to-device (exchange events > 0, zero mid-plan
``d2h``) and the only host gather is the engine's single ``to_host`` at
delivery.

Row-order contract: the expansion writes each output value at its exact
global row-major offset (cumulative-degree position), so emission order is
identical to the single-device backends' and the v2 conformance suite
passes unchanged.

On CPU the mesh is host-count-faked
(``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before importing
jax) so tests and CI exercise the real collective lowering; shard counts
are clamped to the pow2 envelope of the devices actually present, so code
written against ``devices=8`` degrades to a 1-device mesh (collectives
over a world of 1) instead of failing where the flag is unset.
"""
from __future__ import annotations

import functools

import numpy as np

from repro.core.physical_spec import (CostParams, PhysicalSpec,
                                      register_spec)
from repro.graphdb.jax_backend import JaxOperators, _pow2, _pow2_floor
from repro.graphdb.partition import CsrShards, partition_csr

# minimum pow2 capacity of the collective programs' padded shapes: keeps
# the compile universe bounded exactly like the jax backend's tail buckets
_MESH_MIN_BUCKET = 16


class ShardedOperators(JaxOperators):
    """Jax operator set re-based on a device mesh (see module docstring).

    Inherits the jax backend's array primitives, property gathers, int32
    staging envelope and transfer ledger; overrides the pattern operators
    (collective expansion/probing over partitioned CSRs) and the
    relational tail (explicit gather collectives + distributed
    aggregation).  Chains stay on the engine's per-hop loop
    (``supports_chains = False``): each hop is a collective program.
    """

    name = "sharded"
    supports_chains = False
    compiled = True

    def __init__(self, store, devices: int | None = None):
        super().__init__(store)
        import jax
        from jax.sharding import Mesh, PartitionSpec
        avail = len(jax.devices())
        want = avail if devices is None else max(1, min(int(devices), avail))
        self.n_shards = _pow2_floor(want)
        self.mesh = Mesh(np.array(jax.devices()[:self.n_shards]), ("data",))
        self._P = PartitionSpec
        self._lax = jax.lax
        self._shards: dict[int, tuple[CsrShards, tuple]] = {}
        self._progs: dict[tuple, object] = {}

    # ------------------------------------------------------------- plumbing
    def _record_exchange(self, kind: str, label: str, elems: int, n: int = 1):
        for _ in range(n):
            self.exchange_stats.record(kind, label, elems)

    def _smap(self, fn, in_specs, out_specs):
        import jax
        # check_vma=False: psum/pmin/pmax outputs ARE replicated, but the
        # programs also replicate values that never pass through a
        # collective (the all-shards-equal inputs of searchsorted /
        # while_loop bodies), which the varying-axes type check rejects
        return jax.jit(jax.shard_map(fn, mesh=self.mesh,
                                     in_specs=in_specs,
                                     out_specs=out_specs,
                                     check_vma=False))

    def _prog(self, key: tuple, build):
        prog = self._progs.get(key)
        if prog is None:
            prog = self._progs[key] = build()
            self.kernel_stats.record("compile", key[0])
        return prog

    def _csr_shards(self, csr):
        """Partition + upload one CSR's stacked shard blocks (cached by
        CSR identity, like the jax backend's ``_csr_dev``)."""
        ent = self._shards.get(id(csr))
        if ent is None:
            sh = partition_csr(csr, self.n_shards)
            dev = (self._upload(sh.indptr), self._upload(sh.indices),
                   self._upload(sh.pos) if sh.pos is not None else None,
                   self._upload(sh.edge_base))
            ent = self._shards[id(csr)] = (sh, dev)
        return ent

    # -------------------------------------------------- collective expansion
    def _deg_prog(self, fcap: int, rps: int):
        jnp, lax, P = self._jnp, self._lax, self._P

        def kernel(rows, ip_blk):
            s = lax.axis_index("data")
            ipb = ip_blk[0]
            lr = rows - s * rps
            mine = (rows >= 0) & (lr >= 0) & (lr < rps)
            lrc = jnp.clip(lr, 0, rps - 1)
            d = (jnp.take(ipb, lrc + 1, axis=0, mode="clip")
                 - jnp.take(ipb, lrc, axis=0, mode="clip"))
            d = jnp.where(mine, d, 0)
            deg = lax.psum(d, "data")          # frontier degree exchange
            return deg, deg.sum(), deg.astype(jnp.float32).sum()

        return self._smap(kernel, (P(), P("data", None)), (P(), P(), P()))

    def _expand_prog(self, fcap: int, out_cap: int, rps: int, nnz_cap: int,
                     has_pos: bool):
        jnp, lax, P = self._jnp, self._lax, self._P
        i32 = jnp.int32

        def kernel(rows, deg, total, ip_blk, ix_blk, ps_blk, ebase):
            s = lax.axis_index("data")
            ipb, ixb = ip_blk[0], ix_blk[0]
            cum = jnp.cumsum(deg)
            j = jnp.arange(out_cap, dtype=i32)
            i = jnp.searchsorted(cum, j, side="right").astype(i32)
            ic = jnp.minimum(i, fcap - 1)
            off = j - jnp.take(cum - deg, ic, axis=0, mode="clip")
            row = jnp.take(rows, ic, axis=0, mode="clip")
            lr = row - s * rps
            mine = (j < total) & (row >= 0) & (lr >= 0) & (lr < rps)
            lrc = jnp.clip(lr, 0, rps - 1)
            flat = jnp.clip(jnp.take(ipb, lrc, axis=0, mode="clip") + off,
                            0, nnz_cap - 1)
            nbr = jnp.take(ixb, flat, axis=0, mode="clip")
            ep = (jnp.take(ps_blk[0], flat, axis=0, mode="clip") if has_pos
                  else ebase[0] + flat)
            # psum_scatter: combine owner-unique contributions AND leave
            # each device holding its contiguous chunk of the expansion
            sc = functools.partial(lax.psum_scatter, axis_name="data",
                                   scatter_dimension=0, tiled=True)
            return (sc(jnp.where(mine, ic, 0)),
                    sc(jnp.where(mine, nbr, 0)),
                    sc(jnp.where(mine, ep, 0)))

        in_specs = (P(), P(), P(), P("data", None), P("data", None),
                    P("data", None), P("data"))
        return self._smap(kernel, in_specs, (P("data"),) * 3)

    def expand(self, csr, rows_local, max_out=None):
        jnp = self._jnp
        rows = jnp.asarray(rows_local)
        R = rows.shape[0]
        z = jnp.zeros(0, jnp.int32)
        if R == 0:
            return z, z, z
        sh, (ip_d, ix_d, ps_d, eb_d) = self._csr_shards(csr)
        S, rps = self.n_shards, sh.rows_per_shard
        nnz_cap = sh.indices.shape[1]
        fcap = _pow2(R, _MESH_MIN_BUCKET)
        rows_p = self._pad(rows, fcap, -1)      # -1: owned by nobody
        dkey = ("sharded_deg", fcap, rps)
        deg, t0, tf0 = self._prog(dkey, lambda: self._deg_prog(fcap, rps))(
            rows_p, ip_d)
        self.kernel_stats.record("dispatch", "sharded_deg")
        self._record_exchange("psum", "expand_frontier", fcap)
        total, approx = self._sync("sharded_expand", (t0, tf0))
        total = int(total)
        if float(approx) > 2147483391.0:         # int32 sum wrapped
            raise RuntimeError(f"intermediate blow-up: expansion would "
                               f"produce ~{float(approx):.3g} rows (beyond "
                               f"the int32 staging envelope)")
        if max_out is not None and total > max_out:
            raise RuntimeError(f"intermediate blow-up: expansion would "
                               f"produce {total} rows > cap {max_out}")
        if total == 0:
            return z, z, z
        out_cap = _pow2(total, max(_MESH_MIN_BUCKET, S))
        has_pos = ps_d is not None
        ekey = ("sharded_expand", fcap, out_cap, rps, nnz_cap, has_pos)
        prog = self._prog(ekey, lambda: self._expand_prog(
            fcap, out_cap, rps, nnz_cap, has_pos))
        ridx, nbr, ep = prog(rows_p, deg, jnp.asarray(total, jnp.int32),
                             ip_d, ix_d, ps_d if has_pos else ix_d, eb_d)
        self.kernel_stats.record("dispatch", "sharded_expand")
        self._record_exchange("psum_scatter", "expand_emit", out_cap, n=3)
        return ridx[:total], nbr[:total], ep[:total]

    # ---------------------------------------------------- collective probing
    def _probe_prog(self, rcap: int, rps: int, nnz_cap: int, has_pos: bool):
        jnp, lax, P = self._jnp, self._lax, self._P
        from repro.graphdb.jaxops import bounded_binary_search

        def kernel(rows, tgt, ip_blk, ix_blk, ps_blk, ebase):
            s = lax.axis_index("data")
            ipb, ixb = ip_blk[0], ix_blk[0]
            lr = rows - s * rps
            mine = (rows >= 0) & (lr >= 0) & (lr < rps)
            lrc = jnp.clip(lr, 0, rps - 1)
            lo = jnp.take(ipb, lrc, axis=0, mode="clip")
            hi = jnp.take(ipb, lrc + 1, axis=0, mode="clip")
            # -2 never matches a real id (>= 0): non-owned rows probe inert
            found, pos = bounded_binary_search(
                ixb, lo, hi, jnp.where(mine, tgt, -2))
            posc = jnp.clip(pos, 0, nnz_cap - 1).astype(jnp.int32)
            ep = (jnp.take(ps_blk[0], posc, axis=0, mode="clip") if has_pos
                  else ebase[0] + posc)
            hit = mine & found
            return (lax.psum(hit.astype(jnp.int32), "data"),
                    lax.psum(jnp.where(hit, ep, 0), "data"))

        in_specs = (P(), P(), P("data", None), P("data", None),
                    P("data", None), P("data"))
        return self._smap(kernel, in_specs, (P(), P()))

    def intersect(self, csr, rows_local, targets):
        jnp = self._jnp
        rows = jnp.asarray(rows_local)
        tgt = jnp.asarray(targets)
        R = rows.shape[0]
        if R == 0:
            return jnp.zeros(0, bool), jnp.zeros(0, jnp.int32)
        sh, (ip_d, ix_d, ps_d, eb_d) = self._csr_shards(csr)
        rps = sh.rows_per_shard
        nnz_cap = sh.indices.shape[1]
        rcap = _pow2(R, _MESH_MIN_BUCKET)
        has_pos = ps_d is not None
        key = ("sharded_probe", rcap, rps, nnz_cap, has_pos)
        prog = self._prog(key, lambda: self._probe_prog(rcap, rps, nnz_cap,
                                                        has_pos))
        f, ep = prog(self._pad(rows, rcap, -1), self._pad(tgt, rcap, -2),
                     ip_d, ix_d, ps_d if has_pos else ix_d, eb_d)
        self.kernel_stats.record("dispatch", "sharded_probe")
        self._record_exchange("psum", "probe", rcap, n=2)
        found = f[:R] > 0
        return found, jnp.where(found, ep[:R], 0)

    # ------------------------------------------------------- tail collectives
    def _gather_prog(self, padlen: int):
        lax, P = self._lax, self._P

        def kernel(x):
            return lax.all_gather(x, "data", tiled=True)

        return self._smap(kernel, (P("data"),), P())

    def _collect(self, label: str, arrays: list):
        """Gather sharded operand columns to mesh-wide replicas with an
        explicit (recorded) ``all_gather`` per column — the relational
        tail's exchange step."""
        jnp = self._jnp
        out = []
        for a in arrays:
            a = jnp.asarray(a)
            n = a.shape[0]
            if n == 0 or self.n_shards == 1:
                out.append(a)
                continue
            padlen = _pow2(n, max(_MESH_MIN_BUCKET, self.n_shards))
            key = ("sharded_gather", padlen, str(a.dtype))
            prog = self._prog(key, lambda: self._gather_prog(padlen))
            g = prog(self._pad(a, padlen))
            self.kernel_stats.record("dispatch", "sharded_gather")
            self._record_exchange("all_gather", label, padlen)
            out.append(g[:n])
        return out

    def join(self, lkeys, rkeys, max_out=None):
        lk, rk = self._collect("join", [lkeys, rkeys])
        return super().join(lk, rk, max_out=max_out)

    def combine_keys(self, cols: list):
        if len(cols) <= 1:
            return super().combine_keys(cols)
        return super().combine_keys(self._collect("combine_keys", cols))

    def lexsort(self, cols: list):
        return super().lexsort(self._collect("order", cols))

    def distinct_indices(self, key):
        return super().distinct_indices(self._collect("distinct", [key])[0])

    # ------------------------------------------- distributed group aggregation
    def _groupagg_prog(self, npad: int, ng_cap: int, fns: tuple,
                       dtypes: tuple):
        import jax
        jnp, lax, P = self._jnp, self._lax, self._P

        def kernel(gids, rowidx, *cols):
            seg = functools.partial(jax.ops.segment_sum,
                                    num_segments=ng_cap)
            cnt = lax.psum(seg(jnp.ones_like(gids), gids), "data")
            first = lax.pmin(
                jax.ops.segment_min(rowidx, gids, num_segments=ng_cap),
                "data")
            outs = [first, cnt]
            for fn, c in zip(fns, cols):
                if fn == "COUNT":
                    outs.append(cnt)
                elif fn == "SUM":
                    outs.append(lax.psum(seg(c, gids), "data"))
                elif fn == "AVG":
                    s = lax.psum(seg(c.astype(jnp.float32), gids), "data")
                    outs.append(s / jnp.maximum(cnt, 1))
                elif fn == "MIN":
                    outs.append(lax.pmin(
                        jax.ops.segment_min(c, gids, num_segments=ng_cap),
                        "data"))
                else:                                       # MAX
                    outs.append(lax.pmax(
                        jax.ops.segment_max(c, gids, num_segments=ng_cap),
                        "data"))
            return tuple(outs)

        in_specs = (P("data"),) * (2 + len(fns))
        return self._smap(kernel, in_specs, (P(),) * (2 + len(fns)))

    def group_reduce(self, keys, values):
        """Two-phase distributed aggregation: group identities are resolved
        once on gathered keys (ascending-key group ids, exactly the
        single-device backends' group order), then every shard reduces its
        own chunk of the value rows into per-group partials and the mesh
        combines them — ``psum`` for COUNT/SUM/AVG, ``pmin``/``pmax`` for
        MIN/MAX and the first-row index.  Row membership never moves; only
        ``O(n_groups)`` partials cross the mesh per shard."""
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
        keys_g = self._collect("group_keys", [keys])[0]
        np2 = _pow2(n, _MESH_MIN_BUCKET)
        self._tail_compile("group", (np2,))
        self.kernel_stats.record("dispatch", "group")
        order, vstart, _flag_order, ng0 = \
            self._jaxops.group_boundaries_padded(self._pad(keys_g, np2), n)
        ng = int(self._sync("sharded_group", ng0))
        # ascending-rank group id per original row: cumsum over the sorted
        # domain carried back through the inverse permutation
        gid_sorted = jnp.cumsum(vstart.astype(jnp.int32)) - 1
        gids = jnp.take(gid_sorted, jnp.argsort(order), axis=0,
                        mode="clip")[:n]
        ng_cap = _pow2(ng + 1, _MESH_MIN_BUCKET)
        S = self.n_shards
        npad = _pow2(n, max(_MESH_MIN_BUCKET, S))
        names = list(values)
        fns = tuple(values[nm][0] for nm in names)
        cols = [jnp.asarray(values[nm][1]) for nm in names]
        dtypes = tuple(str(c.dtype) for c in cols)
        key = ("sharded_group", npad, ng_cap, fns, dtypes)
        prog = self._prog(key, lambda: self._groupagg_prog(npad, ng_cap,
                                                           fns, dtypes))
        # pads land in the dummy top group slot (ng_cap-1 >= ng) and their
        # row index pads high, so no real group's partials see them
        args = [self._pad(gids, npad, ng_cap - 1),
                self._pad(jnp.arange(n, dtype=jnp.int32), npad, npad)]
        args += [self._pad(c, npad) for c in cols]
        out = prog(*args)
        self.kernel_stats.record("dispatch", "sharded_group")
        n_sum = sum(1 for fn in fns if fn in ("COUNT", "SUM", "AVG"))
        self._record_exchange("psum", "group_reduce", ng_cap, n=1 + n_sum)
        n_min = 1 + sum(1 for fn in fns if fn == "MIN")
        self._record_exchange("pmin", "group_reduce", ng_cap, n=n_min)
        n_max = sum(1 for fn in fns if fn == "MAX")
        if n_max:
            self._record_exchange("pmax", "group_reduce", ng_cap, n=n_max)
        first = out[0][:ng]
        return first, {nm: o[:ng] for nm, o in zip(names, out[2:])}


# --------------------------------------------------------------------------
# registration
# --------------------------------------------------------------------------

# alpha_scan/expand/intersect/join carry over from the jax calibration
# (benchmarks/calibrate_costs.py — same kernels do the local work);
# alpha_exchange is an uncalibrated CPU-faked-mesh placeholder: it prices
# each operator's frontier collective at a few local-work units so the CBO
# visibly trades communication against intersection work.  Re-calibrate on
# a real interconnect (ROADMAP).
SHARDED_COST = CostParams(alpha_scan=1.0, alpha_expand=5.3,
                          alpha_intersect=34.0, alpha_join=1.0,
                          alpha_exchange=2.0)

SHARDED_SPEC = register_spec(PhysicalSpec(
    name="sharded",
    make_operators=ShardedOperators,
    cost=SHARDED_COST,
    description=("mesh-partitioned CSR shards with collective "
                 "(shard_map) expansion/probing, gather-exchanged tail "
                 "kernels and psum-combined aggregation; exchanges "
                 "recorded in ExchangeStats (DESIGN.md §10)"),
))

_DEVICE_SPECS: dict[int, PhysicalSpec] = {}


def sharded_spec(devices: int | None = None) -> PhysicalSpec:
    """The sharded backend's spec pinned to an explicit shard count
    (``GOpt(store, backend="sharded", devices=8)``).  Each count gets its
    own registered spec name (``sharded[8]``) so plan caches and the
    per-store operator cache never mix shard layouts; ``devices=None`` is
    the auto spec over every local device."""
    if devices is None:
        return SHARDED_SPEC
    devices = int(devices)
    spec = _DEVICE_SPECS.get(devices)
    if spec is None:
        spec = PhysicalSpec(
            name=f"sharded[{devices}]",
            make_operators=functools.partial(ShardedOperators,
                                             devices=devices),
            cost=SHARDED_COST,
            description=SHARDED_SPEC.description +
            f" (pinned to {devices} shards)")
        register_spec(spec)
        _DEVICE_SPECS[devices] = spec
    return spec
