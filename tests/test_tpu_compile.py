"""Compile the device path's kernels for a described TPU v5e chip.

Nothing runs: each test lowers and compiles for a chip that is described,
not attached, which raises whatever the chip's compiler would refuse
(unaligned tiles, scoped-VMEM overflow, ops without a TPU lowering).  The
topology is described inside a module fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker running
this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.graphdb import jaxops
from repro.graphdb.jax_backend import _SLAB_ROWS
from repro.kernels.wcoj_intersect.ops import gather_rows
from repro.kernels.wcoj_intersect.wcoj_intersect import (
    TILE_ELEMS, block_rows_for, wcoj_intersect_pallas)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                             # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)


@pytest.mark.parametrize("d_max", [8, 128, 1024])
def test_wcoj_kernel_compiles_for_v5e(one_chip, d_max):
    """The per-operator intersect's program at its largest slab: CSR ->
    lane-dense ELL gather + the Pallas kernel, with the row tile the kernel
    picks for the caller (``block_rows_for``)."""
    rows = _SLAB_ROWS
    block = block_rows_for(rows, d_max)
    assert block == rows or (block % 128 == 0
                             and block * max(d_max, 8) <= TILE_ELEMS)

    def probe(indices, indptr, rows_local, targets):
        adj = gather_rows(indices, indptr, rows_local, d_max)
        return wcoj_intersect_pallas(adj, targets, interpret=False)

    compiled = jax.jit(probe).lower(
        _sds(one_chip, (1 << 22,)), _sds(one_chip, (1 << 18,)),
        _sds(one_chip, (rows,)), _sds(one_chip, (rows,))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 2 * rows * 4


def test_tail_group_kernel_compiles_for_v5e(one_chip):
    """One device relational-tail kernel: sorted-run group boundaries."""
    n = 1 << 10
    compiled = jaxops.group_boundaries_padded.lower(
        _sds(one_chip, (n,)), _sds(one_chip, ())).compile()
    assert compiled.memory_analysis().argument_size_in_bytes >= n * 4
