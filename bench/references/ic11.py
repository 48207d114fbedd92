"""The reference answer of ``ic11``: the full answer, every group
before ORDER BY and LIMIT (``bench/reference.py`` gives the conventions)."""
import numpy as np

from reference import ones


def answer(g, params: dict) -> dict:
    """(p)-[:KNOWS]-(friend)-[:WORKAT]->(org)-[:ISLOCATEDIN]->(c:COUNTRY),
    p.id = $pid: friend, org, count(c)."""
    p = g.local("PERSON", params["pid"])
    if p < 0:
        return {}
    friends = g.row(g.knows_both(), p)
    work = g.out("PERSON", "WORKAT", "ORGANISATION")
    countries = g.out("ORGANISATION", "ISLOCATEDIN", "COUNTRY") \
        @ ones(g.n["COUNTRY"])
    out = {}
    for f in np.flatnonzero(friends):
        for o in work.indices[work.indptr[f]:work.indptr[f + 1]]:
            n = int(friends[f] * countries[o])
            if n:
                out[(int(g.gid("PERSON", f)),
                     int(g.gid("ORGANISATION", o)))] = n
    return out
