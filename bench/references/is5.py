"""The reference answer of ``is5`` (LDBC SNB IS5, a message's creator):
``(m:COMMENT)-[:HASCREATOR]->(p:PERSON), m.id = $mid: p.id,
p.firstName``, one row per creator (rows in any order)."""


def answer(g, params: dict) -> list:
    m = g.local("COMMENT", params["mid"])
    if m < 0:
        return []
    return [(g.prop("PERSON", "id", int(p)),
             g.prop("PERSON", "firstName", int(p)))
            for p in g.targets("COMMENT", "HASCREATOR", "PERSON", m)]
