"""Planner statistics: seconds spent in ``GOpt(store, backend="jax")``
(statistics and the GLogue), host clock."""


def read(run: dict):
    return run["phases"]["gopt_s"]
