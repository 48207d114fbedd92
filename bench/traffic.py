"""The one traffic generator: it reads a mix file (``bench/traffic/*.json``)
and turns it and a seed into requests.

A mix file holds only parameters:

- ``arrivals``: ``{"process": "poisson", "rate": r}`` (requests per
  second), ``{"process": "onoff", "rate": r, "on_s": a, "off_s": b}``
  (the same mean rate, sent only in the on-periods), or
  ``{"process": "closed", "clients": n}`` (each client sends its next
  request when its last one completes);
- ``queries``: each with its ``text``, its ``share`` of the requests, its
  ``anchors`` (one per parameter: the vertex type, ``range`` as fractions
  of that type's ids, and ``dist`` ``uniform`` or ``zipf`` with ``a``),
  the ``reference`` that answers it and the ``result`` shape to compare;
- ``control``: how the control of ``correct`` breaks the exact-answer
  guarantee for this mix's queries (``bench/reference.py``).

Every seed offers the same work, in another order.  A run of ``T``
seconds at rate ``r`` sends ``n = round(r * T)`` requests.  Poisson
arrivals take ``n + 1`` exponential gaps at fixed quantiles, scaled to
fill the window, in an order drawn from the seed (on/off arrivals are
drawn uniformly over the on-periods).  The queries follow the shares
exactly (largest remainder), in an order drawn from the seed, and every
request draws a fresh binding from the seed.  Warm-up bindings come from a
stream of their own, and the window draws again where it would send one
of them.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# SeedSequence spawn keys of the independent streams drawn from one seed
WINDOW, WARMUP, WARM_ORDER = 0, 1, 2


@dataclasses.dataclass
class Request:
    due_s: float         # scheduled send time, from the window's start
    query: int           # index into the mix's ``queries``
    params: dict


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _split(total: int, shares: list) -> list:
    """``total`` requests over ``shares`` by the largest remainder."""
    w = np.asarray(shares, float) / float(sum(shares))
    raw = w * total
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out), kind="stable")[:total - out.sum()]:
        out[i] += 1
    return out.tolist()


def draw_params(rng, query: dict, v_count: dict) -> dict:
    """One binding of ``query``'s parameters."""
    out = {}
    for name, a in query["anchors"].items():
        n = v_count[a["type"]]
        lo = int(n * a["range"][0])
        hi = max(lo + 1, int(n * a["range"][1]))
        if a["dist"] == "uniform":
            out[name] = int(rng.integers(lo, hi))
        elif a["dist"] == "zipf":
            out[name] = lo + int((rng.zipf(a["a"]) - 1) % (hi - lo))
        else:
            raise ValueError(f"unknown anchor distribution {a['dist']!r}")
    return out


def draw_unused(rng, query: dict, v_count: dict, used: set) -> dict:
    """A binding of ``query`` that is not in ``used`` (keys of
    ``binding_key``), drawn like ``draw_params``."""
    for _ in range(1000):
        p = draw_params(rng, query, v_count)
        if binding_key(p) not in used:
            return p
    raise ValueError(f"{query['name']}: no binding outside the "
                     f"{len(used)} used ones in 1000 draws")


def query_order(rng, mix: dict, total: int) -> list:
    """The query index of each of ``total`` requests, by exact shares."""
    counts = _split(total, [q["share"] for q in mix["queries"]])
    order = np.repeat(np.arange(len(counts)), counts)
    rng.shuffle(order)
    return order.tolist()


def arrival_times(rng, arrivals: dict, seconds: float) -> list:
    """Send times within ``[0, seconds)`` of an open-loop process."""
    kind = arrivals["process"]
    total = max(1, int(round(arrivals["rate"] * seconds)))
    if kind == "poisson":
        q = (np.arange(total + 1) + 0.5) / (total + 1)
        gaps = rng.permutation(-np.log1p(-q))
        t = np.cumsum(gaps)[:total] * (seconds / gaps.sum())
    elif kind == "onoff":
        period = arrivals["on_s"] + arrivals["off_s"]
        # positions within the on-time, laid end to end over the periods
        on_total = sum(min(arrivals["on_s"], seconds - s)
                       for s in np.arange(0.0, seconds, period))
        u = rng.uniform(0.0, on_total, size=total)
        t = (u // arrivals["on_s"]) * period + u % arrivals["on_s"]
    else:
        raise ValueError(f"{kind!r} has no arrival times")
    return np.sort(t).tolist()


def schedule(mix: dict, v_count: dict, seed: int, seconds: float,
             used: list | None = None) -> list:
    """The open-loop requests of one run, in send order; ``used`` holds,
    per query, the bindings the window must not send (the warm-up's)."""
    rng = rng_for(seed, WINDOW)
    times = arrival_times(rng, mix["arrivals"], seconds)
    order = query_order(rng, mix, len(times))
    return [Request(t, qi, _fresh(rng, mix, qi, v_count, used))
            for t, qi in zip(times, order)]


def closed_stream(mix: dict, v_count: dict, seed: int, total: int,
                  used: list | None = None) -> list:
    """The first ``total`` requests of a closed loop, in send order (their
    ``due_s`` is set when a client sends them); ``used`` as for
    ``schedule``."""
    rng = rng_for(seed, WINDOW)
    order = query_order(rng, mix, total)
    return [Request(0.0, qi, _fresh(rng, mix, qi, v_count, used))
            for qi in order]


def _fresh(rng, mix: dict, qi: int, v_count: dict, used) -> dict:
    q = mix["queries"][qi]
    if not used:
        return draw_params(rng, q, v_count)
    return draw_unused(rng, q, v_count, used[qi])


def warmup_bindings(mix: dict, v_count: dict, seed: int,
                    per_query: int) -> list:
    """``per_query`` distinct bindings of each query to warm up with, drawn
    like the window's but from a stream of their own."""
    rng = rng_for(seed, WARMUP)
    out = []
    for q in mix["queries"]:
        seen, keys, tries = [], set(), 0
        while len(seen) < per_query and tries < 50 * per_query:
            p = draw_params(rng, q, v_count)
            tries += 1
            if binding_key(p) not in keys:
                keys.add(binding_key(p))
                seen.append(p)
        out.append(seen)
    return out


def binding_key(params: dict) -> tuple:
    return tuple(sorted(params.items()))
