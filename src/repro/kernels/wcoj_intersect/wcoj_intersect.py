"""Pallas TPU kernel: worst-case-optimal-join membership probe.

The expand-and-intersect step of GOpt's WCOJ plans: for every binding-table
row, test whether candidate vertex ``target[i]`` occurs in the sorted
adjacency row ``i`` of a padded-ELL block (-1 padding).

TPU adaptation (DESIGN.md): a GPU WCOJ uses per-thread binary search; on the
TPU VPU a *vectorized compare-scan* over the VMEM-resident adjacency tile
beats serialized log-step gathers for the degree ranges the engine feeds
(D_max <= 1024) — 8x128 vector lanes compare an entire row block per cycle.
The engine splits higher-degree rows before calling.

Layout (lane-dense): adj [D_max, R] int32 — column ``i`` holds row ``i``'s
adjacency, sorted ascending and -1 padded — and target [R] int32.  Rows
ride the 128-wide lane axis and the compare-scan reduces over sublanes, so
every tile and every output block is dense for any D_max (a row-major
[R, D_max] tile would pad small D_max out to 128 lanes, and 1-D per-row
outputs cannot tile to the TPU's layout).  The grid tiles rows; each step
loads [D_max, block_rows] into VMEM and writes found [R] int32 (0/1) and
pos [R] int32 (index within the row, or -1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# int32 elements of one [D_max, block_rows] adjacency tile (2 MiB): at
# D_max = 1024 the double-buffered tile plus the compare temporaries fit
# v5e's scoped VMEM (tests/test_tpu_compile.py compiles it for the chip)
TILE_ELEMS = 1 << 19
_LANES = 128


def block_rows_for(n_rows: int, d_max: int) -> int:
    """Rows per grid step: the whole row range when it fits one tile (a
    block equal to the array is always legal), else the largest pow2 tile
    within ``TILE_ELEMS`` — D_max counts at least 8, the sublane padding of
    a small D_max — and never below one lane width."""
    tile = TILE_ELEMS // max(d_max, 8)
    tile = max(_LANES, 1 << (tile.bit_length() - 1))
    return n_rows if n_rows <= tile else tile


def _kernel(adj_ref, tgt_ref, found_ref, pos_ref):
    adj = adj_ref[...]                       # [D, TR]: one row per lane
    d = adj.shape[0]
    slot = jax.lax.broadcasted_iota(jnp.int32, adj.shape, 0)
    # rows are sorted & unique, so at most one slot hits and the masked min
    # is its position (d = no hit); argmax does not lower on int/bool
    pos = jnp.min(jnp.where(adj == tgt_ref[...], slot, d), axis=0,
                  keepdims=True)
    found = pos < d
    found_ref[...] = found.astype(jnp.int32)
    pos_ref[...] = jnp.where(found, pos, -1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wcoj_intersect_pallas(adj: jax.Array, target: jax.Array, *,
                          interpret: bool):
    """adj [D, R] int32 lane-dense ELL (-1 pad); target [R] int32.
    Returns (found [R] int32, pos [R] int32)."""
    D, R = adj.shape
    block_rows = block_rows_for(R, D)
    pad = (-R) % block_rows
    if pad:
        adj = jnp.pad(adj, ((0, 0), (0, pad)), constant_values=-1)
        target = jnp.pad(target, (0, pad), constant_values=-2)
    Rp = R + pad
    row_block = pl.BlockSpec((1, block_rows), lambda i: (0, i))
    found, pos = pl.pallas_call(
        _kernel,
        grid=(Rp // block_rows,),
        in_specs=[pl.BlockSpec((D, block_rows), lambda i: (0, i)),
                  row_block],
        out_specs=[row_block, row_block],
        out_shape=[jax.ShapeDtypeStruct((1, Rp), jnp.int32),
                   jax.ShapeDtypeStruct((1, Rp), jnp.int32)],
        interpret=interpret,
        name="wcoj_intersect",
    )(adj, target.reshape(1, Rp))
    return found[0, :R], pos[0, :R]
