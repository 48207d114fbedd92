"""The reference answer of ``qc2b_person``: the full answer, every group
before ORDER BY and LIMIT (``bench/reference.py`` gives the conventions)."""
import numpy as np



def answer(g, params: dict) -> dict:
    """Qc2b with person1.id = $pid: count(person1) over
    (person1)-[:LIKES]->(message:POST)<-[:CONTAINEROF]-(person2:FORUM),
    (person1)-[:KNOWS|HASINTEREST]->(place:PERSON|TAG),
    (person2)-[:HASMODERATOR|HASTAG]->(place)."""
    p = g.local("PERSON", params["pid"])
    if p < 0:
        return {(): 0}
    liked = g.row(g.out("PERSON", "LIKES", "POST"), p)
    forums = g.inn("FORUM", "CONTAINEROF", "POST").T @ liked
    places = (g.out("FORUM", "HASMODERATOR", "PERSON")
              @ g.row(g.out("PERSON", "KNOWS", "PERSON"), p)
              + g.out("FORUM", "HASTAG", "TAG")
              @ g.row(g.out("PERSON", "HASINTEREST", "TAG"), p))
    return {(): int(np.dot(forums, places))}
