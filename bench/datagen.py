"""The benchmark's own data generator: an LDBC SNB-shaped social network
sized from a configuration file (``bench/configs/*.json``).

The data is the yardstick's input, so it lives here and not in the program:
a change to the program cannot move what a cell serves.  A configuration
gives each vertex type's count and each edge triple's count at SF1 (the
LDBC SNB specification's published statistics) and a scale ``sf``:

- a vertex type has ``count`` vertices at SF1; types with ``"scale":
  false`` are LDBC's fixed dictionaries (tags, places, organisations) and
  keep their count at every scale, the others scale by ``sf``;
- an edge triple has ``count`` edges at SF1, scaled with its source type
  (``src: "each"`` gives every source exactly one edge, ``dst: "each"``
  every target exactly one, ``src: "split"`` gives every source exactly one
  edge over the triples of its label that say so, in the shares of their
  counts);
- a free end is drawn ``uniform``, or ``power``: id ``i`` with weight
  ``(i + 1) ** -power_b``, so the popular vertices (hubs) sit at the low ids
  of a type.

Edges are distinct and free of self-loops, and every count is exact: every
seed gives the same number of vertices and edges of every triple, wired
differently.  ``generate(cfg, seed)`` returns plain numpy arrays; the
program receives them through its public ``build_store`` and the reference
(``bench/reference.py``) reads them directly.
"""
from __future__ import annotations

import zlib

import numpy as np

_FIRST_NAMES = ["Jan", "Yang", "Maria", "Ahmed", "Li", "Anna", "Jose", "Ken"]
_DATE_LO, _DATE_HI = 1_262_304_000, 1_356_998_400


def vertex_counts(cfg: dict) -> dict:
    """Vertices per type at the configuration's scale."""
    sf = cfg["sf"]
    return {t: int(round(v["count"] * (sf if v.get("scale", True) else 1)))
            for t, v in cfg["vertices"].items()}


def edge_count(cfg: dict, e: dict, n: dict) -> int:
    """Edges of one triple: its SF1 count, scaled as its source type."""
    s_ty = e["triple"][0]
    if e.get("dst") == "each":
        return n[e["triple"][2]]
    if e.get("src") == "each":
        return n[s_ty]
    sf1 = cfg["vertices"][s_ty]["count"]
    return int(round(e["count"] * n[s_ty] / sf1))


def _rng(seed: int, *tag) -> np.random.Generator:
    key = [int(seed)] + [zlib.crc32(t.encode()) if isinstance(t, str)
                         else int(t) for t in tag]
    return np.random.default_rng(np.random.SeedSequence(key))


def _draw(rng, dist: str, k: int, n: int, b: float) -> np.ndarray:
    if dist == "uniform":
        return rng.integers(0, n, size=k, dtype=np.int64)
    if dist == "power":
        cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -b)
        return np.minimum(np.searchsorted(cdf, rng.random(k) * cdf[-1],
                                          side="right"), n - 1)
    raise ValueError(f"unknown end distribution {dist!r}")


def _no_loops(src, dst, same: bool, n_dst: int) -> np.ndarray:
    if same:
        dst = np.where(dst == src, (dst + 1) % n_dst, dst)
    return dst


def _distinct_pairs(rng, m: int, ns: int, nd: int, dst_dist: str,
                    a: float, same: bool) -> tuple:
    """``m`` distinct ``(src, dst)`` pairs, sources uniform, in draw order."""
    keys = np.zeros(0, dtype=np.int64)
    while len(keys) < m:
        k = int((m - len(keys)) * 1.25) + 1024
        s = rng.integers(0, ns, size=k, dtype=np.int64)
        d = _no_loops(s, _draw(rng, dst_dist, k, nd, a), same, nd)
        keys = np.concatenate([keys, s * nd + d])
        _, first = np.unique(keys, return_index=True)
        keys = keys[np.sort(first)]
    keys = keys[:m]
    return keys // nd, keys % nd


def generate(cfg: dict, seed: int) -> dict:
    """The data set of one configuration file:
    ``{"n": {type: count}, "edges": {(src, label, dst): (src, dst)},
    "v_props": ..., "e_props": {(src, label, dst): {...}}, "vocab": ...}``
    with local ids.  Deterministic per ``(cfg, seed)``."""
    a = cfg["power_b"]
    n = vertex_counts(cfg)
    edges = {}
    splits: dict = {}
    for e in cfg["edges"]:
        if e.get("src") == "split":
            splits.setdefault((e["triple"][0], e["triple"][1]), []).append(e)
    for key, group in splits.items():
        # every source one edge, over the group's triples by their counts
        ns = n[key[0]]
        rng = _rng(seed, "split", *key)
        order = rng.permutation(ns)
        w = np.cumsum([g["count"] for g in group], dtype=float)
        cuts = np.round(w / w[-1] * ns).astype(np.int64)
        lo = 0
        for g, hi in zip(group, cuts):
            t = tuple(g["triple"])
            src = np.sort(order[lo:hi])
            nd = n[t[2]]
            dst = _no_loops(src, _draw(rng, g["dst"], len(src), nd, a),
                            t[0] == t[2], nd)
            edges[t] = (src, dst)
            lo = hi
    for e in cfg["edges"]:
        t = tuple(e["triple"])
        if t in edges:
            continue
        ns, nd = n[t[0]], n[t[2]]
        rng = _rng(seed, "e", *t)
        if e.get("src") == "each":
            src = np.arange(ns, dtype=np.int64)
            dst = _no_loops(src, _draw(rng, e["dst"], ns, nd, a),
                            t[0] == t[2], nd)
        elif e.get("dst") == "each":
            dst = np.arange(nd, dtype=np.int64)
            src = _no_loops(dst, _draw(rng, e["src"], nd, ns, a),
                            t[0] == t[2], ns)
        else:
            src, dst = _distinct_pairs(rng, edge_count(cfg, e, n), ns, nd,
                                       e["dst"], a, t[0] == t[2])
        edges[t] = (src, dst)

    vocab = {"name": {}, "firstName": {}}

    def ints(tag, k, lo, hi):
        return _rng(seed, "p", tag).integers(lo, hi, size=k, dtype=np.int64)

    def names(prefix, k):
        v = vocab["name"]
        return np.array([v.setdefault(f"{prefix}_{i}", len(v))
                         for i in range(k)], dtype=np.int64)

    fn = vocab["firstName"]
    for name in _FIRST_NAMES:
        fn.setdefault(name, len(fn))
    ids = {t: np.arange(k, dtype=np.int64) for t, k in n.items()}
    v_props = {
        "PERSON": {"id": ids["PERSON"],
                   "firstName": ints("firstName", n["PERSON"], 0,
                                     len(_FIRST_NAMES)),
                   "creationDate": ints("d.PERSON", n["PERSON"], _DATE_LO,
                                        _DATE_HI)},
        "POST": {"id": ids["POST"],
                 "length": ints("len.POST", n["POST"], 0, 256),
                 "creationDate": ints("d.POST", n["POST"], _DATE_LO,
                                      _DATE_HI)},
        "COMMENT": {"id": ids["COMMENT"],
                    "length": ints("len.COMMENT", n["COMMENT"], 0, 256),
                    "creationDate": ints("d.COMMENT", n["COMMENT"],
                                         _DATE_LO, _DATE_HI)},
        "FORUM": {"id": ids["FORUM"],
                  "creationDate": ints("d.FORUM", n["FORUM"], _DATE_LO,
                                       _DATE_HI)},
        "TAG": {"id": ids["TAG"], "name": names("tag", n["TAG"])},
        "TAGCLASS": {"id": ids["TAGCLASS"],
                     "name": names("class", n["TAGCLASS"])},
        "CITY": {"id": ids["CITY"], "name": names("city", n["CITY"])},
        "COUNTRY": {"id": ids["COUNTRY"],
                    "name": names("country", n["COUNTRY"])},
        "ORGANISATION": {"id": ids["ORGANISATION"],
                         "name": names("org", n["ORGANISATION"])},
    }
    knows = ("PERSON", "KNOWS", "PERSON")
    e_props = {knows: {"creationDate": ints(
        "d.KNOWS", len(edges[knows][0]), _DATE_LO, _DATE_HI)}}
    return {"n": n, "edges": edges, "v_props": v_props, "e_props": e_props,
            "vocab": vocab}


def build_program_store(data: dict):
    """Hand the generated arrays to the program: its schema and its
    ``build_store``, the one way the program takes a data set in."""
    from repro.core.schema import EdgeTriple, ldbc_schema
    from repro.graphdb.storage import build_store
    return build_store(
        ldbc_schema(), data["n"],
        {EdgeTriple(*t): se for t, se in data["edges"].items()},
        data["v_props"],
        {EdgeTriple(*t): p for t, p in data["e_props"].items()},
        data["vocab"])
