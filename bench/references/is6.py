"""The reference answer of ``is6`` (LDBC SNB IS6, the forum of a post and
its moderator): ``(m:POST)<-[:CONTAINEROF]-(f:FORUM)-[:HASMODERATOR]->
(mod:PERSON), m.id = $mid: f.id, mod.id, mod.firstName``, one row per
(forum, moderator) pair (rows in any order)."""


def answer(g, params: dict) -> list:
    m = g.local("POST", params["mid"])
    if m < 0:
        return []
    return [(g.prop("FORUM", "id", int(f)),
             g.prop("PERSON", "id", int(p)),
             g.prop("PERSON", "firstName", int(p)))
            for f in g.sources("FORUM", "CONTAINEROF", "POST", m)
            for p in g.targets("FORUM", "HASMODERATOR", "PERSON", int(f))]
