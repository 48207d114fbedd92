"""The reference answer of ``ic3``: the full answer, every group
before ORDER BY and LIMIT (``bench/reference.py`` gives the conventions)."""
import numpy as np

from reference import groups, ones


def _messages_tags_per_creator(g) -> np.ndarray:
    """Per person: the (message, tag) pairs of the messages they created."""
    def build():
        per = np.zeros(g.n["PERSON"], np.int64)
        for m_ty in ("POST", "COMMENT"):
            tags = g.out(m_ty, "HASTAG", "TAG") @ ones(g.n["TAG"])
            per += g.inn(m_ty, "HASCREATOR", "PERSON") @ tags
        return per
    return g.cached("msg_tags", build)


def answer(g, params: dict) -> dict:
    """(p)-[:KNOWS]-(friend)<-[:HASCREATOR]-(m:POST|COMMENT)-[:HASTAG]->(t),
    p.id = $pid: friend, count(m)."""
    p = g.local("PERSON", params["pid"])
    if p < 0:
        return {}
    friends = g.row(g.knows_both(), p)
    return groups("PERSON", g, friends * _messages_tags_per_creator(g))
