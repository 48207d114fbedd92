"""jit'd JAX mirrors of the engine's hot primitives.

On TPU these (and their Pallas variants in ``repro.kernels``) execute the
fixed-shape inner loops of pattern matching; the numpy twins in ``vecops`` are
the host path. Shapes must be static under jit, so the expansion primitive
works on a padded row block and returns a validity mask — the same contract
the Pallas kernels use.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("max_degree",))
def expand_padded(indptr: jax.Array, indices: jax.Array,
                  rows_local: jax.Array, max_degree: int):
    """Expand each row to at most ``max_degree`` neighbors.

    Returns (nbr[R, max_degree], valid[R, max_degree], flat_pos[R, max_degree]).
    Rows with degree > max_degree are truncated (caller splits such rows).
    """
    start = indptr[rows_local]
    deg = indptr[rows_local + 1] - start
    offs = jnp.arange(max_degree, dtype=indptr.dtype)[None, :]
    valid = offs < deg[:, None]
    flat = jnp.clip(start[:, None] + offs, 0, indices.shape[0] - 1)
    nbr = jnp.where(valid, indices[flat], -1)
    return nbr, valid, jnp.where(valid, flat, -1)


@jax.jit
def bounded_binary_search(indices: jax.Array, lo: jax.Array, hi: jax.Array,
                          targets: jax.Array):
    """jnp twin of vecops.bounded_binary_search (found, pos)."""
    hi_orig = hi
    n = indices.shape[0]

    def cond(state):
        lo, hi = state
        return jnp.any(lo < hi)

    def body(state):
        lo, hi = state
        active = lo < hi
        mid = (lo + hi) // 2
        v = indices[jnp.minimum(mid, n - 1)]
        go_right = active & (v < targets)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
        return lo, hi

    lo, hi = jax.lax.while_loop(cond, body, (lo, hi))
    pos = lo
    in_range = pos < jnp.minimum(hi_orig, n)
    found = in_range & (indices[jnp.minimum(pos, n - 1)] == targets)
    return found, pos


def range_flatten(start: jax.Array, counts: jax.Array, total: int):
    """Row-major flattening of per-row index ranges ``[start_i, start_i +
    counts_i)``: returns ``(row_idx[total], flat_pos[total])``.

    The device twin of the ``np.repeat``-based expansion in
    ``vecops.expand_csr`` — built from cumsum + searchsorted + gathers
    because both ``jnp.repeat`` and scatter-based alternatives serialize
    (or pay heavy eager machinery) on CPU XLA.  ``total`` is the
    data-dependent output size, synced by the caller and static under jit.
    """
    cum = jnp.cumsum(counts)
    pos = jnp.arange(total, dtype=jnp.int32)
    ridx = jnp.searchsorted(cum, pos, side="right").astype(jnp.int32)
    offs = pos - jnp.take(cum - counts, ridx, axis=0, mode="clip")
    flat = jnp.take(start, ridx, axis=0, mode="clip") + offs
    return ridx, flat


@jax.jit
def csr_expand_total(indptr: jax.Array, rows: jax.Array):
    """Predictive output size of a CSR expansion (one dispatch; the caller
    syncs it for the blow-up guard and the static expand shape).  Returns
    ``(total_i32, total_f32)``: the int32 sum is exact below 2^31 but
    wraps above it, so the float32 estimate lets the caller catch the
    wrap and still raise the blow-up guard instead of silently building
    an empty/garbled expansion."""
    deg = (jnp.take(indptr, rows + 1, axis=0, mode="clip")
           - jnp.take(indptr, rows, axis=0, mode="clip"))
    return deg.sum(), deg.astype(jnp.float32).sum()


@functools.partial(jax.jit, static_argnames=("total", "has_pos"))
def csr_expand_flat(indptr: jax.Array, indices: jax.Array, pos: jax.Array,
                    rows: jax.Array, total: int, has_pos: bool):
    """Fused expand step: degree lookup + row-major flattening + neighbor /
    edge-position gathers in ONE dispatch (eager would be ~10).  Keyed by
    (rows.shape, total); the caller syncs ``total`` from the degrees first.
    ``pos`` is ignored (pass ``indices``) when ``has_pos`` is False."""
    start = jnp.take(indptr, rows, axis=0, mode="clip")
    deg = jnp.take(indptr, rows + 1, axis=0, mode="clip") - start
    ridx, flat = range_flatten(start, deg, total)
    nbr = jnp.take(indices, flat, axis=0, mode="clip")
    epos = jnp.take(pos, flat, axis=0, mode="clip") if has_pos else flat
    return ridx, nbr, epos


@jax.jit
def lex_ranks(cols: list[jax.Array]) -> jax.Array:
    """Dense lexicographic ranks of row tuples (``cols[0]`` most
    significant): equal tuples share a rank, and rank order equals the
    tuples' lexicographic sort order — the device-native equivalent of
    ``vecops.combine_keys``'s factorized packing (identical grouping and
    identical ascending order, so cross-backend row order is preserved).

    Sort/gather-shaped on purpose: a scatter (``.at[order].set``)
    serializes on CPU XLA, so the group ids are carried back through an
    argsort-based inverse permutation.  jit'd into one dispatch, keyed by
    (n, len(cols)).
    """
    n = cols[0].shape[0]
    if n == 0:
        return jnp.zeros(0, jnp.int32)
    order = jnp.lexsort(tuple(reversed(cols)))
    ne = jnp.zeros(n - 1, bool)
    for c in cols:
        s = jnp.take(c, order, axis=0, mode="clip")
        ne = ne | (s[1:] != s[:-1])
    gid_sorted = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(ne.astype(jnp.int32))])
    inv_order = jnp.argsort(order)
    return jnp.take(gid_sorted, inv_order, axis=0, mode="clip")


# ------------------------------------------------------- bucketed tail twins
# The compound tail kernels below jit on *padded* pow2 shapes: the caller
# pads its inputs up to a capacity bucket and passes the true row count
# ``n_valid`` as a traced scalar, so jittered serving-wave sizes re-hit one
# compiled program per bucket instead of re-tracing per exact shape.  Pad
# rows are ordered strictly last by an explicit pad-flag used as the
# *primary* lexsort key (never by a sentinel value, which real data could
# collide with); every output is exact on ``[:n_valid]`` / ``[:n_groups]``
# and the caller slices the pads away.


def _pad_flag(n: int, n_valid) -> jax.Array:
    return jnp.arange(n, dtype=jnp.int32) >= n_valid


@jax.jit
def lex_ranks_padded(cols: list[jax.Array], n_valid) -> jax.Array:
    """``lex_ranks`` over pow2-padded columns: pad rows sort after every
    valid tuple (pad-flag primary) and land on ranks >= the valid rank
    count, so ``[:n_valid]`` of the result equals the unpadded ranks."""
    n = cols[0].shape[0]
    pf = _pad_flag(n, n_valid)
    order = jnp.lexsort(tuple(reversed(cols)) + (pf,))
    ne = jnp.zeros(n - 1, bool)
    for c in list(cols) + [pf]:
        s = jnp.take(c, order, axis=0, mode="clip")
        ne = ne | (s[1:] != s[:-1])
    gid_sorted = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(ne.astype(jnp.int32))])
    inv_order = jnp.argsort(order)
    return jnp.take(gid_sorted, inv_order, axis=0, mode="clip")


@jax.jit
def group_boundaries(keys: jax.Array):
    """Stage 1 of sorted-run grouping: stable sort by key and flag run
    starts.  Returns ``(order, start_flags, flag_order, n_groups0d)`` — the
    caller syncs ``n_groups`` and slices ``flag_order[:n_groups]`` to get
    the run-start positions (ascending, since argsort is stable)."""
    n = keys.shape[0]
    order = jnp.argsort(keys)
    sk = jnp.take(keys, order, axis=0, mode="clip")
    flags = jnp.concatenate([jnp.ones(1, bool), sk[1:] != sk[:-1]])
    flag_order = jnp.argsort(~flags)
    return order, flags, flag_order, flags.sum()


@jax.jit
def group_boundaries_padded(keys: jax.Array, n_valid):
    """``group_boundaries`` over a pow2-padded key column.  Pad rows sort
    last (pad-flag primary, stable within each side) and never start a
    counted run; ``n_groups`` counts valid runs only, and
    ``flag_order[:n_groups]`` are their ascending sorted-domain starts."""
    n = keys.shape[0]
    pf = _pad_flag(n, n_valid)
    order = jnp.lexsort((keys, pf))
    sk = jnp.take(keys, order, axis=0, mode="clip")
    spf = jnp.take(pf, order, axis=0, mode="clip")
    flags = jnp.concatenate(
        [jnp.ones(1, bool), (sk[1:] != sk[:-1]) | (spf[1:] != spf[:-1])])
    vstart = flags & ~spf
    flag_order = jnp.argsort(~vstart)
    return order, vstart, flag_order, vstart.sum()


# ------------------------------------------------------------ double-single
# Widened SUM/AVG accumulation (x64 is disabled): values are carried as
# exact (hi, lo) float32 pairs — "double-single" arithmetic — and the
# running prefix is built with a compensated TwoSum combiner under
# ``lax.associative_scan`` (log-depth, vectorized; no scatter).  Group sums
# are then boundary differences of the compensated prefix, so the error is
# ~2^-48 *relative to the running total* instead of float32's 2^-24 (and
# int32 SUM no longer wraps just because the running total across all
# preceding groups passed 2^31 — only a group's own total exceeding the
# int32 output envelope is unrepresentable).

def _ds_from_col(col):
    """Exact double-single representation of an int32/float32 column.
    Integers split as ``v = (v >> 12 << 12) + (v & 0xFFF)``: a multiple of
    4096 with <= 19 significant bits plus a 12-bit remainder — both sides
    exact in float32 across the whole int32 range."""
    if col.dtype.kind == "f":
        return col.astype(jnp.float32), jnp.zeros_like(col, jnp.float32)
    hi = ((col >> 12) << 12).astype(jnp.float32)
    lo = (col & 0xFFF).astype(jnp.float32)
    return hi, lo


def _ds_add(a, b):
    """Compensated (TwoSum + renormalize) double-single addition."""
    ah, al = a
    bh, bl = b
    s = ah + bh
    bv = s - ah
    err = (ah - (s - bv)) + (bh - bv)
    t = al + bl + err
    hi = s + t
    return hi, t - (hi - s)


# largest float32 below 2^31: clamping the hi word here keeps the int32
# reconstruction exact (the clamp shift folds into the low word)
_F32_I32_EDGE = 2147483520.0


def _ds_to_int32(hi, lo):
    c = jnp.clip(hi, -_F32_I32_EDGE, _F32_I32_EDGE)
    return c.astype(jnp.int32) + (lo + (hi - c)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("fns",))
def group_aggregate(order: jax.Array, starts: jax.Array, keys: jax.Array,
                    cols: tuple, fns: tuple):
    """Stage 2 of sorted-run grouping, one dispatch for every aggregate:
    counts via boundary differences, SUM/AVG via a compensated
    double-single prefix scan (see ``_ds_add`` — exact while running totals
    stay within ~2^48, vs the naive float32 cumsum that drifted once the
    running total across *all* groups grew large), MIN/MAX via a secondary
    value sort within key runs.  ``fns`` is the static aggregate spec
    aligned with ``cols``.  SUM results are exact whenever the group's own
    total fits the int32 output envelope."""
    n = order.shape[0]
    bounds = jnp.concatenate([starts, jnp.asarray([n], starts.dtype)])
    ends = bounds[1:] - 1
    counts = (bounds[1:] - bounds[:-1]).astype(jnp.int32)
    first = jnp.take(order, starts, axis=0, mode="clip")
    outs = []
    for fn, col in zip(fns, cols):
        if fn == "COUNT":
            outs.append(counts)
            continue
        if fn in ("SUM", "AVG"):
            sorted_col = jnp.take(col, order, axis=0, mode="clip")
            ch, cl = jax.lax.associative_scan(_ds_add, _ds_from_col(sorted_col))
            eh = jnp.take(ch, ends, axis=0, mode="clip")
            el = jnp.take(cl, ends, axis=0, mode="clip")
            ph = jnp.concatenate([jnp.zeros(1, jnp.float32), eh[:-1]])
            pl = jnp.concatenate([jnp.zeros(1, jnp.float32), el[:-1]])
            sh, sl = _ds_add((eh, el), (-ph, -pl))
            outs.append((sh + sl) / jnp.maximum(counts, 1)
                        if fn == "AVG" else _ds_to_int32(sh, sl))
            continue
        # MIN/MAX: secondary sort by value within each key run — minima at
        # run starts, maxima at run ends
        sv = jnp.take(col, jnp.lexsort((col, keys)), axis=0, mode="clip")
        outs.append(jnp.take(sv, starts if fn == "MIN" else ends,
                             axis=0, mode="clip"))
    return first, tuple(outs)


@functools.partial(jax.jit, static_argnames=("fns",))
def group_aggregate_padded(order: jax.Array, starts: jax.Array,
                           keys: jax.Array, n_valid, cols: tuple, fns: tuple):
    """``group_aggregate`` over pow2-padded inputs: ``order``/``keys``/
    ``cols`` are padded to one row bucket (pads sorted last in ``order``),
    ``starts`` is padded to a pow2 group bucket with the terminal bound
    ``n_valid`` — so dummy trailing groups have count 0 and every real
    group's boundary math is untouched.  Outputs are exact on
    ``[:n_groups]``; the caller slices the dummy groups away.  Keyed by
    (row bucket, group bucket, fns)."""
    n = order.shape[0]
    pf = _pad_flag(n, n_valid)
    nv = jnp.asarray(n_valid, starts.dtype)
    bounds = jnp.concatenate([starts, nv[None]])
    ends = bounds[1:] - 1
    counts = (bounds[1:] - bounds[:-1]).astype(jnp.int32)
    first = jnp.take(order, starts, axis=0, mode="clip")
    outs = []
    for fn, col in zip(fns, cols):
        if fn == "COUNT":
            outs.append(counts)
            continue
        if fn in ("SUM", "AVG"):
            sorted_col = jnp.take(col, order, axis=0, mode="clip")
            ch, cl = jax.lax.associative_scan(_ds_add, _ds_from_col(sorted_col))
            eh = jnp.take(ch, ends, axis=0, mode="clip")
            el = jnp.take(cl, ends, axis=0, mode="clip")
            ph = jnp.concatenate([jnp.zeros(1, jnp.float32), eh[:-1]])
            pl = jnp.concatenate([jnp.zeros(1, jnp.float32), el[:-1]])
            sh, sl = _ds_add((eh, el), (-ph, -pl))
            outs.append((sh + sl) / jnp.maximum(counts, 1)
                        if fn == "AVG" else _ds_to_int32(sh, sl))
            continue
        # MIN/MAX secondary value sort: the pad flag stays primary so pad
        # rows cannot land inside a valid key run regardless of value
        sv = jnp.take(col, jnp.lexsort((col, keys, pf)), axis=0, mode="clip")
        outs.append(jnp.take(sv, starts if fn == "MIN" else ends,
                             axis=0, mode="clip"))
    return first, tuple(outs)


@jax.jit
def sortmerge_bounds(lkeys: jax.Array, rkeys: jax.Array):
    """Stage 1 of the sort-merge join (one dispatch): stable sorts + the
    per-left-row matching right range.  Returns ``(lorder, rorder, lo,
    cnt, total0d)``; the caller syncs ``total`` for the pair expansion."""
    lorder = jnp.argsort(lkeys)
    rorder = jnp.argsort(rkeys)
    ls = jnp.take(lkeys, lorder, axis=0, mode="clip")
    rs = jnp.take(rkeys, rorder, axis=0, mode="clip")
    lo = jnp.searchsorted(rs, ls, side="left")
    cnt = jnp.searchsorted(rs, ls, side="right") - lo
    # int32 total (exact below 2^31) + float32 estimate (wrap detector)
    return lorder, rorder, lo, cnt, cnt.sum(), cnt.astype(jnp.float32).sum()


@jax.jit
def sortmerge_bounds_padded(lkeys: jax.Array, rkeys: jax.Array,
                            n_left, n_right):
    """``sortmerge_bounds`` over pow2-padded key columns.  The caller pads
    both sides with INT32_MAX so the right sorted column stays globally
    non-decreasing for ``searchsorted``; the pad flag (primary sort key)
    pins pads to the tail even when real keys equal the pad value, the
    match range is clamped to the valid right prefix, and pad left rows
    contribute zero matches."""
    L = lkeys.shape[0]
    lpf = _pad_flag(L, n_left)
    rpf = _pad_flag(rkeys.shape[0], n_right)
    lorder = jnp.lexsort((lkeys, lpf))
    rorder = jnp.lexsort((rkeys, rpf))
    ls = jnp.take(lkeys, lorder, axis=0, mode="clip")
    rs = jnp.take(rkeys, rorder, axis=0, mode="clip")
    lo = jnp.minimum(jnp.searchsorted(rs, ls, side="left"), n_right)
    hi = jnp.minimum(jnp.searchsorted(rs, ls, side="right"), n_right)
    cnt = jnp.where(jnp.arange(L, dtype=jnp.int32) < n_left, hi - lo, 0)
    # int32 total (exact below 2^31) + float32 estimate (wrap detector)
    return lorder, rorder, lo, cnt, cnt.sum(), cnt.astype(jnp.float32).sum()


@functools.partial(jax.jit, static_argnames=("total",))
def sortmerge_pairs(lorder: jax.Array, rorder: jax.Array, lo: jax.Array,
                    cnt: jax.Array, total: int):
    """Fused pair expansion of the sort-merge join (one dispatch).

    ``total`` may be a pow2 bucket >= the true pair count: positions past
    ``sum(cnt)`` produce clipped garbage pairs the caller slices away."""
    lrep, rpos = range_flatten(lo, cnt, total)
    return (jnp.take(lorder, lrep, axis=0, mode="clip").astype(jnp.int32),
            jnp.take(rorder, rpos, axis=0, mode="clip").astype(jnp.int32))


# --------------------------------------------------------------------------
# Fused chain programs (DESIGN.md §8)
# --------------------------------------------------------------------------
# One ExpandChainNode = ONE compiled program: every hop's degree lookup,
# row-major flattening, neighbor/edge gathers, trailing WCOJ membership
# probes, and folded predicate masks trace into a single jit dispatch.
# Data-dependent sizes stay on device: each hop writes into a *static
# capacity* (``caps[k]``, pow2-bucketed by the backend), rows beyond a
# hop's true total are dead slots carried by a validity mask, and filtered
# rows simply contribute zero degree to the next hop — so emission order is
# exactly the per-hop loop's orientation-major, row-major order without any
# mid-program compaction.  The program returns the padded columns (valid
# rows compacted to the front by one stable argsort), the true row count,
# and the per-hop totals the caller syncs once — for the blow-up guard and
# to grow the capacity schedule when a hop overflowed.

_CHAIN_CMP = {"=": lambda a, b: a == b, "<>": lambda a, b: a != b,
              "<": lambda a, b: a < b, ">": lambda a, b: a > b,
              "<=": lambda a, b: a <= b, ">=": lambda a, b: a >= b}

_CHAIN_I32_MIN = -2147483648


def build_fused_chain(desc: tuple, caps: tuple, in_bucket: int,
                      interpret: bool, empty_values: tuple = ()):
    """Build the traced whole-chain function for one static chain shape.

    ``desc`` = ``(source_col, hops)``; each hop is ``(from_col, alias,
    edge_alias, orients, probes, pred)`` with orients ``(lo, tidx,
    has_pos)``, probes ``(from_col, edge_alias, lo, hi, vlo, vhi, tidx,
    has_pos, mode, d_max)`` and ``pred`` a resolved predicate signature whose
    column refs are ``("col", name) | ("vprop", name, idx) | ("eprop",
    edge_alias, idx)`` and whose leaves read runtime slots.  The caller
    jits the result; the jit cache is keyed by (desc, caps, in_bucket)
    through the builder's own memoization, so recurring bucketed shapes
    never re-trace."""
    from repro.kernels.wcoj_intersect.ops import gather_rows, wcoj_intersect
    source_col, hops = desc
    i32 = jnp.int32

    def eval_ref(ref, cols, vprops, eprops):
        if ref[0] == "col":
            return cols[ref[1]]
        if ref[0] == "vprop":
            _, name, pidx = ref
            return jnp.take(vprops[pidx], cols[name], axis=0, mode="clip")
        _, ealias, pidx = ref
        offsets, flat = eprops[pidx]
        if flat.shape[0] == 0:
            return jnp.full(cols[f"{ealias}#p"].shape, _CHAIN_I32_MIN, i32)
        base = jnp.take(offsets, cols[f"{ealias}#t"], axis=0, mode="clip")
        return jnp.take(flat, base + cols[f"{ealias}#p"], axis=0,
                        mode="clip")

    def eval_pred(sig, cols, scalars, values, vprops, eprops):
        kind = sig[0]
        if kind == "cmp":
            _, op, ref, slot = sig
            return _CHAIN_CMP[op](eval_ref(ref, cols, vprops, eprops),
                                  scalars[slot])
        if kind == "in":
            _, ref, vidx = sig
            lhs = eval_ref(ref, cols, vprops, eprops)
            if vidx in empty_values:     # static: empty IN-set matches nothing
                return jnp.zeros(lhs.shape, bool)
            return jnp.isin(lhs, values[vidx])
        if kind == "not":
            return ~eval_pred(sig[1][0], cols, scalars, values, vprops,
                              eprops)
        acc = eval_pred(sig[1][0], cols, scalars, values, vprops, eprops)
        for s in sig[1][1:]:
            m = eval_pred(s, cols, scalars, values, vprops, eprops)
            acc = (acc & m) if kind == "and" else (acc | m)
        return acc

    def run(src, n0, csrs, vprops, eprops, scalars, values):
        cols = {"__rows": jnp.arange(in_bucket, dtype=i32), source_col: src}
        valid = jnp.arange(in_bucket, dtype=i32) < n0
        needed, needed_f = [], []
        for k, (from_col, alias, ealias, orients, probes, pred) in \
                enumerate(hops):
            cap = caps[k]
            frm = cols[from_col]
            degs, row_starts = [], []
            for j, (lo, hi, tidx, has_pos) in enumerate(orients):
                indptr = csrs[k][0][j][0]
                local = jnp.clip(frm - lo, 0, indptr.shape[0] - 2)
                s0 = jnp.take(indptr, local, axis=0, mode="clip")
                d = jnp.take(indptr, local + 1, axis=0, mode="clip") - s0
                # the keyed-type range membership mask: rows of a
                # mixed-type frontier outside [lo, hi) expand to nothing,
                # exactly like the per-hop loop's nonzero() subset
                in_range = valid & (frm >= lo) & (frm < hi)
                degs.append(jnp.where(in_range, d, 0))
                row_starts.append(s0)
            totals, offs = [], []
            running = jnp.asarray(0, i32)
            for d in degs:
                offs.append(running)
                totals.append(d.sum().astype(i32))
                running = running + totals[-1]
            needed.append(running)
            needed_f.append(sum(d.astype(jnp.float32).sum() for d in degs))
            pos_out = jnp.arange(cap, dtype=i32)
            acc_r = jnp.zeros(cap, i32)
            acc_nbr = jnp.zeros(cap, i32)
            acc_tv = jnp.zeros(cap, i32)
            acc_p = jnp.zeros(cap, i32)
            for j, (lo, hi, tidx, has_pos) in enumerate(orients):
                _, indices, pos = csrs[k][0][j]
                in_j = (pos_out >= offs[j]) & (pos_out < offs[j] + totals[j])
                lp = pos_out - offs[j]
                cum = jnp.cumsum(degs[j])
                r = jnp.searchsorted(cum, lp, side="right").astype(i32)
                o = lp - jnp.take(cum - degs[j], r, axis=0, mode="clip")
                flat = jnp.take(row_starts[j], r, axis=0, mode="clip") + o
                nb = jnp.take(indices, flat, axis=0, mode="clip")
                ep = (jnp.take(pos, flat, axis=0, mode="clip") if has_pos
                      else flat)
                acc_r = jnp.where(in_j, r, acc_r)
                acc_nbr = jnp.where(in_j, nb, acc_nbr)
                acc_tv = jnp.where(in_j, tidx, acc_tv)
                acc_p = jnp.where(in_j, ep, acc_p)
            cols = {nm: jnp.take(c, acc_r, axis=0, mode="clip")
                    for nm, c in cols.items()}
            cols[alias] = acc_nbr
            cols[f"{ealias}#t"] = acc_tv
            cols[f"{ealias}#p"] = acc_p
            valid = pos_out < jnp.minimum(running, cap)
            for pj, (p_from, p_ealias, lo, hi, vlo, vhi, tidx, has_pos,
                     mode, d_max) in enumerate(probes):
                indptr, indices, pos = csrs[k][1][pj]
                pfrm = cols[p_from]
                local = jnp.clip(pfrm - lo, 0, indptr.shape[0] - 2)
                # rows outside the keyed/value type ranges fail the probe
                # (the per-hop loop's membership masks); -2 never matches
                # a real id (>= 0) or an ELL pad (-1)
                ok = (valid & (pfrm >= lo) & (pfrm < hi)
                      & (cols[alias] >= vlo) & (cols[alias] < vhi))
                tgt = jnp.where(ok, cols[alias], -2)
                if mode == "ell":
                    adj = gather_rows(indices, indptr, local, d_max)
                    found, prow = wcoj_intersect(adj, tgt,
                                                 interpret=interpret)
                    fpos = (jnp.take(indptr, local, axis=0, mode="clip")
                            + prow.astype(i32))
                else:
                    lo_b = jnp.take(indptr, local, axis=0, mode="clip")
                    hi_b = jnp.take(indptr, local + 1, axis=0, mode="clip")
                    found, fpos = bounded_binary_search(indices, lo_b, hi_b,
                                                        tgt)
                ep = (jnp.take(pos, fpos.astype(i32), axis=0, mode="clip")
                      if has_pos else fpos.astype(i32))
                cols[f"{p_ealias}#t"] = jnp.full(cap, tidx, i32)
                cols[f"{p_ealias}#p"] = jnp.where(found, ep, 0)
                valid = valid & found
            if pred is not None:
                valid = valid & eval_pred(pred, cols, scalars, values,
                                          vprops, eprops)
        order = jnp.argsort(~valid).astype(i32)   # stable: valid rows first
        out = {nm: jnp.take(c, order, axis=0, mode="clip")
               for nm, c in cols.items()}
        return (out, valid.sum().astype(i32), jnp.stack(needed),
                jnp.stack(needed_f))

    return run


@jax.jit
def segment_count(segment_ids: jax.Array, num_segments: int):
    return jax.ops.segment_sum(jnp.ones_like(segment_ids), segment_ids,
                               num_segments=num_segments)


@functools.partial(jax.jit, static_argnames=("num_segments",))
def group_count(keys: jax.Array, num_segments: int):
    """Count per dense key in [0, num_segments)."""
    return jax.ops.segment_sum(
        jnp.ones(keys.shape[0], jnp.int32), keys, num_segments=num_segments)
