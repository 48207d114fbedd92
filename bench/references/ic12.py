"""The reference answer of ``ic12``: the full answer, every group
before ORDER BY and LIMIT (``bench/reference.py`` gives the conventions)."""
import numpy as np

from reference import groups, ones


def _reply_chains_per_creator(g) -> np.ndarray:
    """Per person: the (comment, post, tag, tagclass) rows of the comments
    they created that reply to a post."""
    def build():
        classes = g.out("TAG", "HASTYPE", "TAGCLASS") @ ones(g.n["TAGCLASS"])
        per_post = g.out("POST", "HASTAG", "TAG") @ classes
        per_comment = g.out("COMMENT", "REPLYOF", "POST") @ per_post
        return g.inn("COMMENT", "HASCREATOR", "PERSON") @ per_comment
    return g.cached("reply_chains", build)


def answer(g, params: dict) -> dict:
    """(p)-[:KNOWS]-(friend)<-[:HASCREATOR]-(comment:COMMENT)
    -[:REPLYOF]->(post:POST)-[:HASTAG]->(t)-[:HASTYPE]->(tc),
    p.id = $pid: friend, count(comment)."""
    p = g.local("PERSON", params["pid"])
    if p < 0:
        return {}
    friends = g.row(g.knows_both(), p)
    return groups("PERSON", g, friends * _reply_chains_per_creator(g))
