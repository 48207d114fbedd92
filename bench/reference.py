"""The plain reference: the answers of the cells' queries, computed from the
generated arrays (``bench/datagen.py``) with numpy and scipy sparse
matrices, independently of the program.

Pattern semantics are homomorphic, as in the program's query language:
every pattern vertex binds one graph vertex, distinct pattern vertices may
bind the same one, every pattern edge binds one stored edge of a triple its
labels and endpoint types allow, and an undirected edge binds either
direction (an edge stored both ways binds twice).  ``count(x)`` counts the
matched rows of its group.  Stored edges are a set per triple.

Each query's reference is a file of its own, ``bench/references/<name>.py``
(the name a traffic mix gives under ``reference``), whose
``answer(g, params)`` returns the query's full answer: ``{group key tuple:
value}`` for a grouped ``RETURN ... count()`` (every group, before ORDER BY
and LIMIT), ``{(): count}`` for a bare count, or the list of row tuples of
a plain ``RETURN`` of properties.  ``bench/compare.py`` holds a served
table against it.

A ``Graph`` built with a ``control`` breaks the configurations' exact-answer
guarantee, as the control of ``correct`` does: ``{"cap": k}`` cuts every
adjacency list, in either direction, to its first ``k`` neighbours (as a
fixed-width ELL row without overflow handling would see it);
``{"props": "float32"}`` reads every property through float32, the
narrower type a device column would tempt one to keep.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np
import scipy.sparse as sp

HERE = Path(__file__).resolve().parent


class Graph:
    """The data set as sparse 0/1 matrices per triple and direction, with
    the global id layout the program's answers use (types in the
    configuration's order, each a contiguous range)."""

    def __init__(self, data: dict, control: dict | None = None):
        control = control or {}
        self.n = dict(data["n"])
        self.v_props = data["v_props"]
        self.offset, off = {}, 0
        for t, k in self.n.items():
            self.offset[t] = off
            off += k
        self.edges = data["edges"]
        self.cap = control.get("cap")
        self.props_dtype = control.get("props")
        self._mats: dict = {}
        self._deg: dict = {}

    def _matrix(self, triple: tuple, reverse: bool) -> sp.csr_matrix:
        key = (triple, reverse)
        if key not in self._mats:
            s_ty, _, d_ty = triple
            src, dst = self.edges[triple]
            ns, nd = self.n[s_ty], self.n[d_ty]
            pairs = np.unique(np.asarray(src, np.int64) * nd
                              + np.asarray(dst, np.int64))
            src, dst = pairs // nd, pairs % nd
            if reverse:
                src, dst, ns, nd = dst, src, nd, ns
            order = np.lexsort((dst, src))
            src, dst = src[order], dst[order]
            if self.cap is not None:
                starts = np.searchsorted(src, src, side="left")
                keep = np.arange(src.size) - starts < self.cap
                src, dst = src[keep], dst[keep]
            m = sp.csr_matrix((np.ones(src.size, np.int64), (src, dst)),
                              shape=(ns, nd))
            self._mats[key] = m
        return self._mats[key]

    def out(self, s_ty: str, label: str, d_ty: str) -> sp.csr_matrix:
        """``[src, dst]`` = 1 where the edge is stored (rows: sources)."""
        return self._matrix((s_ty, label, d_ty), False)

    def inn(self, s_ty: str, label: str, d_ty: str) -> sp.csr_matrix:
        """``[dst, src]`` = 1 where the edge is stored (rows: targets)."""
        return self._matrix((s_ty, label, d_ty), True)

    def row(self, m: sp.csr_matrix, i: int) -> np.ndarray:
        """Row ``i`` of ``m`` as a dense int64 vector."""
        return np.asarray(m.getrow(i).todense(), np.int64).ravel()

    def knows_both(self) -> sp.csr_matrix:
        """Undirected KNOWS: ``[a, b]`` counts the stored edges that join
        them, one per direction."""
        key = "knows_both"
        if key not in self._mats:
            self._mats[key] = (self.out("PERSON", "KNOWS", "PERSON")
                               + self.inn("PERSON", "KNOWS", "PERSON")).tocsr()
        return self._mats[key]

    def gid(self, vtype: str, local) -> np.ndarray:
        return np.asarray(local, np.int64) + self.offset[vtype]

    def local(self, vtype: str, vid: int) -> int:
        """The vertex whose ``id`` property is ``vid`` (ids are 0..n-1 per
        type), or -1 when no such vertex exists."""
        return int(vid) if 0 <= int(vid) < self.n[vtype] else -1

    def prop(self, vtype: str, name: str, local: int) -> int:
        """Property ``name`` of vertex ``local`` of ``vtype``."""
        v = self.v_props[vtype][name][local]
        if self.props_dtype is not None:
            v = np.asarray(v).astype(self.props_dtype).astype(np.float64)
        return int(v)

    def targets(self, s_ty: str, label: str, d_ty: str, local: int
                ) -> np.ndarray:
        """The out-neighbours of ``local`` over one triple."""
        m = self.out(s_ty, label, d_ty)
        return m.indices[m.indptr[local]:m.indptr[local + 1]]

    def sources(self, s_ty: str, label: str, d_ty: str, local: int
                ) -> np.ndarray:
        """The in-neighbours of ``local`` over one triple."""
        m = self.inn(s_ty, label, d_ty)
        return m.indices[m.indptr[local]:m.indptr[local + 1]]

    def cached(self, key: str, fn):
        if key not in self._deg:
            self._deg[key] = fn()
        return self._deg[key]


def ones(k: int) -> np.ndarray:
    return np.ones(k, np.int64)


def groups(vtype: str, g: Graph, counts: np.ndarray) -> dict:
    nz = np.flatnonzero(counts)
    return {(int(k),): int(v) for k, v in zip(g.gid(vtype, nz), counts[nz])}


def load(name: str, root: Path = HERE):
    """The reference ``answer`` of query ``name``,
    ``bench/references/<name>.py`` under ``root``."""
    path = root / "references" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reference {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.answer
