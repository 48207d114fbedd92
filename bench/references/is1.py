"""The reference answer of ``is1`` (LDBC SNB IS1, a person's profile):
``(n:PERSON)-[:ISLOCATEDIN]->(c:CITY), n.id = $pid: n.firstName,
n.creationDate, c.id``, one row per city (rows in any order)."""


def answer(g, params: dict) -> list:
    n = g.local("PERSON", params["pid"])
    if n < 0:
        return []
    return [(g.prop("PERSON", "firstName", n),
             g.prop("PERSON", "creationDate", n),
             g.prop("CITY", "id", int(c)))
            for c in g.targets("PERSON", "ISLOCATEDIN", "CITY", n)]
