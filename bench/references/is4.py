"""The reference answer of ``is4`` (LDBC SNB IS4, a message's content):
``(m:COMMENT), m.id = $mid: m.creationDate, m.length``."""


def answer(g, params: dict) -> list:
    m = g.local("COMMENT", params["mid"])
    if m < 0:
        return []
    return [(g.prop("COMMENT", "creationDate", m),
             g.prop("COMMENT", "length", m))]
