"""The reference answer of ``ic1``: the full answer, every group
before ORDER BY and LIMIT (``bench/reference.py`` gives the conventions)."""
import numpy as np

from reference import groups


def answer(g, params: dict) -> dict:
    """(p)-[:KNOWS*2]-(friend), p.id = $pid: friend, count(p)."""
    p = g.local("PERSON", params["pid"])
    if p < 0:
        return {}
    a = g.knows_both()
    walks = g.row(a, p) @ a            # 2-walks from p, per end vertex
    return groups("PERSON", g, np.asarray(walks, np.int64).ravel())
