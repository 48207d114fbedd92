"""The reference answer of ``qc4b_forum``: the full answer, every group
before ORDER BY and LIMIT (``bench/reference.py`` gives the conventions)."""
import numpy as np



def answer(g, params: dict) -> dict:
    """Qc4b with forum.id = $fid: count(person1) over
    (forum)-[:HASTAG]->(post:TAG), (forum)-[:HASMODERATOR]->(person1),
    (forum)-[:HASMODERATOR|CONTAINEROF]->(person2:PERSON|POST),
    (person1)-[:KNOWS|LIKES]->(person2), (person1)-[:HASINTEREST]->(post),
    (person2)-[:HASINTEREST|HASTAG]->(post)."""
    f = g.local("FORUM", params["fid"])
    if f < 0:
        return {(): 0}
    tags = g.row(g.out("FORUM", "HASTAG", "TAG"), f)
    mods = g.row(g.out("FORUM", "HASMODERATOR", "PERSON"), f)
    posts = g.row(g.out("FORUM", "CONTAINEROF", "POST"), f)
    interest = g.out("PERSON", "HASINTEREST", "TAG")
    post_tags = g.out("POST", "HASTAG", "TAG")
    knows = g.out("PERSON", "KNOWS", "PERSON")
    likes = g.out("PERSON", "LIKES", "POST")
    total = 0
    for p1 in np.flatnonzero(mods):
        t1 = tags * mods[p1] * g.row(interest, p1)     # (post:TAG) choices
        # person2 a moderator the moderator knows, interested in the tag
        p2 = mods * g.row(knows, p1)
        total += int(t1 @ (interest.T @ p2))
        # person2 a post of the forum the moderator likes, tagged with it
        q2 = posts * g.row(likes, p1)
        total += int(t1 @ (post_tags.T @ q2))
    return {(): total}
