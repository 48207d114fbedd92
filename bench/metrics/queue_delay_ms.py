"""Serving: the median queue delay (``ServeRequest.queue_delay_s``, from
the scheduled arrival to its wave's start) of the window's answered
requests, in ms."""
from stats import percentile


def read(run: dict):
    d = [r.queue_delay_s * 1e3 for r in run["records"]
         if r.status == "done"]
    return percentile(d, 50) if d else None
