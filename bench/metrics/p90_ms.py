"""Serving: the 90th percentile latency over every request sent in the
window, each timed from when it was sent (scheduled, in an open loop) to
its table on the host, in ms (host clock)."""
import stats


def read(run: dict):
    return stats.end_to_end(run["records"], run["window_s"])["p90_ms"]
