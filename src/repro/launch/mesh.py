"""Production mesh construction.

Must stay a FUNCTION (importing this module never touches jax device state).
Single pod: 16x16 = 256 chips ("data", "model"); multi-pod: 2x16x16 = 512
("pod", "data", "model") — the pod axis is pure data parallelism whose
all-reduce crosses DCN.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (tests / smoke runs)."""
    n = len(jax.devices())
    return _make_mesh((n, 1), ("data", "model"))
