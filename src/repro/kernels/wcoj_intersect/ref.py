"""Pure-jnp oracle for the WCOJ membership probe (lane-dense [D, R] adj)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def wcoj_intersect_ref(adj: jax.Array, target: jax.Array):
    eq = adj == target[None, :]
    found = jnp.any(eq, axis=0)
    pos = jnp.where(found, jnp.argmax(eq, axis=0).astype(jnp.int32), -1)
    return found.astype(jnp.int32), pos
