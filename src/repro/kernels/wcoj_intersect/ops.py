"""The WCOJ probe's entry point and the CSR -> lane-dense padded-ELL row
materialization it takes."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.wcoj_intersect.wcoj_intersect import wcoj_intersect_pallas


def wcoj_intersect(adj: jax.Array, target: jax.Array, *, interpret: bool):
    """``interpret`` is the caller's to choose (the jax operator set runs
    the kernel compiled on a TPU): nothing here falls back to interpret
    mode on its own."""
    return wcoj_intersect_pallas(adj, target, interpret=interpret)


def gather_rows(indices: jax.Array, indptr: jax.Array, rows: jax.Array,
                d_max: int) -> jax.Array:
    """CSR rows -> lane-dense padded ELL [d_max, R] (column ``i`` is row
    ``rows[i]``'s adjacency, -1 padded): the kernel's input layout."""
    start = indptr[rows]
    deg = indptr[rows + 1] - start
    offs = jnp.arange(d_max)[:, None]
    valid = offs < deg[None, :]
    flat = jnp.clip(start[None, :] + offs, 0, indices.shape[0] - 1)
    return jnp.where(valid, indices[flat], -1)
