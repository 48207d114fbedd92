"""ArchBundle: uniform interface every assigned architecture implements.

A bundle knows, per input shape:
- ``input_specs(shape)``      — ShapeDtypeStruct stand-ins for every input of
  the lowered step (weak-type-correct, shardable, no allocation);
- ``abstract_state(shape)``   — SDS pytrees for params / optimizer / caches;
- ``make_step(shape)``        — the jit-able step callable;
- ``shardings(mesh, shape)``  — (in_shardings, out_shardings, hint table)
  NamedSharding pytrees for the production mesh;
- ``make_concrete(shape)``    — real (small) arrays for smoke tests.

launch/dryrun.py composes these into lower().compile() for every
(arch x shape x mesh) cell.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.train import optimizer as opt_mod


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str                     # train | prefill | decode | serve | retrieval
    dims: dict
    skip: str | None = None       # reason string when cell is skipped


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def ns(mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(int(x) for x in shape), dtype)


def map_sds(tree):
    return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)


class ArchBundle:
    arch_id: str = ""
    family: str = ""              # lm | gnn | recsys
    shapes: dict[str, ShapeSpec] = {}

    # ---- to implement ----------------------------------------------------
    def init_params_abstract(self):
        raise NotImplementedError

    def make_step(self, shape: str) -> Callable:
        raise NotImplementedError

    def input_specs(self, shape: str):
        """Full argument tuple (SDS pytrees) for make_step(shape)."""
        raise NotImplementedError

    def shardings(self, mesh, shape: str):
        """(in_shardings, out_shardings, hints) for make_step(shape)."""
        raise NotImplementedError

    def make_concrete(self, shape: str, seed: int = 0):
        """Real small arrays for smoke testing (only for smoke bundles)."""
        raise NotImplementedError

    # ---- common ----------------------------------------------------------
    def adam_cfg(self) -> opt_mod.AdamWConfig:
        return opt_mod.AdamWConfig()

    def abstract_adam_state(self, params_sds):
        return jax.eval_shape(lambda p: opt_mod.init(self.adam_cfg(), p),
                              params_sds)

    def model_flops(self, shape: str) -> float:
        """Analytic MODEL_FLOPS for the §Roofline table (global, per step)."""
        return 0.0

    def shape_names(self) -> list[str]:
        return list(self.shapes)


def params_spec_like(tree, fn) -> Any:
    """Build a sharding pytree by mapping fn(path_tuple, leaf_sds)->P."""
    flat, treedef = jax.tree.flatten_with_path(tree)
    specs = [fn(tuple(str(k) for k in path), leaf) for path, leaf in flat]
    return jax.tree.unflatten(treedef, specs)


def zero1(spec: P, shape, data_size: int, mesh) -> P:
    """ZeRO-1: add 'data' sharding to an optimizer-state leaf on the first
    axis that is unsharded and divisible by the data-axis size."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    if "data" in [p for p in parts if p]:
        return P(*parts)
    for i, (p, d) in enumerate(zip(parts, shape)):
        if p is None and d % data_size == 0 and d >= data_size:
            parts[i] = "data"
            return P(*parts)
    return P(*parts)


def replicate_tree(mesh, tree):
    return jax.tree.map(lambda _: ns(mesh), tree)


def metrics_sharding(mesh, metrics_sds):
    return jax.tree.map(lambda _: ns(mesh), metrics_sds)


def to_jnp(tree):
    return jax.tree.map(jnp.asarray, tree)


def rand_tokens(rng: np.random.Generator, shape, vocab: int):
    return rng.integers(0, vocab, size=shape).astype(np.int32)
