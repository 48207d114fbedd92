"""Percentile arithmetic and the end-to-end numbers of one window."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100), interpolated linearly between the
    closest ranks (numpy's default, ``statistics.quantiles``' inclusive
    method)."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of no values")
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def end_to_end(requests, window_s: float) -> dict:
    """p50/p90 latency over every request sent in the window, each timed
    from its scheduled send time to its table on the host (a request that
    never came is timed to when the run gave up on it), and the requests
    completed inside the window per second of window.

    ``requests``: records with ``due_s``, ``host_s`` (``None`` when it
    never came) and ``gave_up_s``, all on the window's clock."""
    lat = [((r.host_s if r.host_s is not None else r.gave_up_s) - r.due_s)
           * 1e3 for r in requests]
    done_in_window = sum(1 for r in requests
                         if r.host_s is not None and r.host_s <= window_s)
    return {"p50_ms": percentile(lat, 50), "p90_ms": percentile(lat, 90),
            "completed_qps": done_in_window / window_s}
