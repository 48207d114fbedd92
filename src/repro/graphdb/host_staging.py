"""Host-staging adapter — the pre-v2 (PR 3) executor↔backend contract,
preserved verbatim as a measurable baseline.

``HostStagingOperators`` reproduces the PR-3 era jax data plane exactly:
binding-table columns live in host numpy, the relational tail runs on the
host path, and the pattern kernels run on device *per call* — uploading the
row block, materializing the padded ``[R, D_max]`` neighbor/validity blocks
that jit's static shapes demand, downloading those padded blocks, and
compacting them back to flat rows **on the host**.  All transfers register
on the wrapped set's ``TransferStats``, so ``benchmarks/perf_compare.py
--residency`` can put a number on exactly what OperatorSet v2 removes
(zero mid-plan ``d2h``, no padded-block round trips), query by query,
against the device-resident path.
"""
from __future__ import annotations

import numpy as np

from repro.core.physical_spec import OperatorSet
from repro.graphdb import jax_backend as _jb
from repro.graphdb.numpy_backend import NumpyOperators


_pow2 = _jb._pow2        # the device path's rounding, not a diverging copy


class HostStagingOperators(NumpyOperators):
    """PR-3-style round-trip execution over a device operator set."""

    def __init__(self, inner: OperatorSet):
        super().__init__(inner.store)
        self.inner = inner
        self.name = f"host_staged[{inner.name}]"
        # shared ledger: the wrapper's per-op round trips show up exactly
        # where the device backend would have avoided them
        self.transfer_stats = inner.transfer_stats

    # PR-3 helpers: host pad + recorded up/downloads -----------------------
    @staticmethod
    def _pad_rows(a: np.ndarray, n: int, fill) -> np.ndarray:
        out = np.full(n, fill, dtype=a.dtype)
        out[:a.shape[0]] = a
        return out

    def _up(self, a: np.ndarray):
        return self.inner.asarray(a)

    def _down(self, x) -> np.ndarray:
        return np.asarray(self.inner.to_host(x))

    # ------------------------------------------------------------- expand
    def expand(self, csr, rows_local, max_out=None):
        """PR-3 expand: jit'd padded block on device, flattened on host."""
        rows_local = np.asarray(rows_local, dtype=np.int64)
        R = rows_local.shape[0]
        deg = csr.indptr[rows_local + 1] - csr.indptr[rows_local]
        total = int(deg.sum())
        if max_out is not None and total > max_out:
            raise RuntimeError(f"intermediate blow-up: expansion would "
                               f"produce {total} rows > cap {max_out}")
        if total == 0:
            z = np.zeros(0, dtype=np.int64)
            return z, z, z
        parts = []
        for s in range(0, R, _jb._SLAB_ROWS):
            e = min(s + _jb._SLAB_ROWS, R)
            self._expand_chunk(csr, rows_local[s:e], deg[s:e], s, parts)
        ridx = np.concatenate([p[0] for p in parts])
        nbr = np.concatenate([p[1] for p in parts])
        fpos = np.concatenate([p[2] for p in parts])
        epos = csr.pos[fpos] if csr.pos is not None else fpos
        return ridx, nbr, epos

    def _expand_chunk(self, csr, rows_local, deg, base, parts):
        """Halve the chunk while the padded [rows, d_max] block would bust
        the element budget (verbatim PR-3 degree-skew isolation)."""
        if int(deg.sum()) == 0:
            return
        d_hi = int(deg.max())
        R = rows_local.shape[0]
        if R > 1 and (_pow2(R, _jb._MIN_BLOCK_ROWS) * _pow2(d_hi)
                      > _jb._EXPAND_ELEMS):
            h = R // 2
            self._expand_chunk(csr, rows_local[:h], deg[:h], base, parts)
            self._expand_chunk(csr, rows_local[h:], deg[h:], base + h, parts)
            return
        ridx, nbr, fpos = self._expand_slab(csr, rows_local, d_hi)
        parts.append((ridx + base, nbr, fpos))

    def _expand_slab(self, csr, rows_local, d_hi):
        indptr_d, indices_d, _pos = self.inner._csr_dev(csr)
        d_max = _pow2(d_hi)
        rp = _pow2(rows_local.shape[0], _jb._MIN_BLOCK_ROWS)
        rows_p = self._pad_rows(rows_local, rp, 0).astype(np.int32)
        nbr, valid, flat = self.inner._jaxops.expand_padded(
            indptr_d, indices_d, self._up(rows_p), d_max)
        # PR-3 compaction: download the PADDED blocks, flatten on host
        R = rows_local.shape[0]
        valid = self._down(valid)[:R]
        ridx, _slot = np.nonzero(valid)
        nbr_flat = self._down(nbr)[:R][valid].astype(np.int64)
        fpos = self._down(flat)[:R][valid].astype(np.int64)
        return ridx.astype(np.int64), nbr_flat, fpos

    # ---------------------------------------------------------- intersect
    def intersect(self, csr, rows_local, targets):
        rows_local = np.asarray(rows_local, dtype=np.int64)
        targets = np.asarray(targets, dtype=np.int64)
        R = rows_local.shape[0]
        found = np.zeros(R, dtype=bool)
        fpos = np.zeros(R, dtype=np.int64)
        if R == 0:
            return found, fpos
        deg = csr.indptr[rows_local + 1] - csr.indptr[rows_local]
        for s in range(0, R, _jb._SLAB_ROWS):
            e = min(s + _jb._SLAB_ROWS, R)
            d_hi = int(deg[s:e].max())
            if d_hi == 0:
                continue
            if d_hi <= _jb.MAX_ELL_DEGREE:
                f, p = self._intersect_ell(csr, rows_local[s:e],
                                           targets[s:e], d_hi)
            else:
                f, p = self._intersect_bsearch(csr, rows_local[s:e],
                                               targets[s:e])
            found[s:e] = f
            fpos[s:e] = p
        epos = np.zeros(R, dtype=np.int64)
        if found.any():
            hp = fpos[found]
            epos[found] = csr.pos[hp] if csr.pos is not None else hp
        return found, epos

    def _intersect_ell(self, csr, rows_local, targets, d_hi):
        from repro.kernels.wcoj_intersect.ops import gather_rows
        indptr_d, indices_d, _pos = self.inner._csr_dev(csr)
        d_max = _pow2(d_hi)
        R = rows_local.shape[0]
        rp = _pow2(R, _jb._MIN_BLOCK_ROWS)
        rows_p = self._pad_rows(rows_local, rp, 0).astype(np.int32)
        tgt_p = self._pad_rows(targets, rp, -2).astype(np.int32)
        adj = gather_rows(indices_d, indptr_d, self._up(rows_p), d_max)
        found_d, pos_d = self.inner._wcoj(adj, self._up(tgt_p),
                                          interpret=self.inner._interpret)
        found = self._down(found_d)[:R].astype(bool)
        pos_in_row = self._down(pos_d)[:R].astype(np.int64)
        return found, csr.indptr[rows_local] + pos_in_row

    def _intersect_bsearch(self, csr, rows_local, targets):
        indptr_d, indices_d, _pos = self.inner._csr_dev(csr)
        R = rows_local.shape[0]
        rp = _pow2(R, _jb._MIN_BLOCK_ROWS)
        lo = self._pad_rows(csr.indptr[rows_local], rp, 0).astype(np.int32)
        hi = self._pad_rows(csr.indptr[rows_local + 1], rp,
                            0).astype(np.int32)
        tgt = self._pad_rows(targets, rp, -2).astype(np.int32)
        found_d, pos_d = self.inner._jaxops.bounded_binary_search(
            indices_d, self._up(lo), self._up(hi), self._up(tgt))
        found = self._down(found_d)[:R].astype(bool)
        return found, self._down(pos_d)[:R].astype(np.int64)
