"""Minimal functional parameter system.

Models declare parameters as ``Spec`` trees (shape + dtype + *logical axis
names* + initializer).  From one spec tree we derive:

  - ``init_params``      — materialized arrays (smoke tests, real training),
  - ``abstract_params``  — ``jax.ShapeDtypeStruct`` stand-ins for the
                           multi-pod dry-run (never allocates),
  - ``logical_axes``     — pytree of axis-name tuples consumed by
                           ``distributed/sharding.py`` to build
                           ``NamedSharding``s from the mesh rules.

No flax/haiku dependency: params are plain nested dicts of arrays, models are
pure functions — the natural fit for ``jax.jit`` + ``lax.scan`` over stacked
layers.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Spec", "init_params", "abstract_params", "logical_axes", "tree_bytes"]

Pytree = Any


@dataclasses.dataclass(frozen=True)
class Spec:
    """Declaration of one parameter tensor."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axis per dim (None = replicated)
    init: str = "normal"  # normal | zeros | ones | scaled (fan-in)
    scale: float = 1.0
    dtype: Any = jnp.float32
    # inputs each output sums over, for init="scaled"; None: shape[-2]
    # (right for an (in, out) matrix, wrong for e.g. (d, heads, head_dim))
    fan_in: Optional[int] = None

    def __post_init__(self) -> None:
        if len(self.shape) != len(self.axes):
            raise ValueError(
                f"spec shape {self.shape} and axes {self.axes} rank mismatch"
            )


def _init_one(key: jax.Array, spec: Spec, dtype: Any) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "normal":
        return (spec.scale * jax.random.normal(key, spec.shape)).astype(dtype)
    if spec.init == "scaled":  # fan-in scaled (truncated-normal-ish)
        fan_in = spec.fan_in or (
            spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        )
        std = spec.scale / math.sqrt(max(1, fan_in))
        return (std * jax.random.normal(key, spec.shape)).astype(dtype)
    raise ValueError(f"unknown init {spec.init!r}")


def _is_spec(x: Any) -> bool:
    return isinstance(x, Spec)


def init_params(
    specs: Pytree, key: jax.Array, dtype: Optional[Any] = None
) -> Pytree:
    """Materialize a spec tree into arrays (deterministic per path)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    arrays = [
        _init_one(k, s, dtype or s.dtype) for k, s in zip(keys, leaves, strict=True)
    ]
    return jax.tree.unflatten(treedef, arrays)


def abstract_params(specs: Pytree, dtype: Optional[Any] = None) -> Pytree:
    """ShapeDtypeStruct stand-ins — the dry-run path, zero allocation."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype or s.dtype),
        specs,
        is_leaf=_is_spec,
    )


def logical_axes(specs: Pytree) -> Pytree:
    """Pytree of logical-axis tuples, same structure as the params."""
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=_is_spec)


def tree_bytes(tree: Pytree) -> int:
    """Total bytes of a tree of arrays or ShapeDtypeStructs."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        total += int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
    return total
