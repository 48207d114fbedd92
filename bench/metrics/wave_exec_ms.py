"""Executor: the median execution time of the window's waves
(``ServeStats.exec_s``), in ms."""
from stats import percentile


def read(run: dict):
    ex = [s * 1e3 for s in run["waves"]["exec_s"]]
    return percentile(ex, 50) if ex else None
