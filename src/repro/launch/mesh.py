"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
touches no jax device state — required because the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first jax
initialization, while smoke tests and benchmarks see the real single device.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """A mesh whose axes are all ``Auto``: sharding is propagated by XLA from
    the ``with_sharding_constraint`` hints in the model code (an ``Explicit``
    axis, ``jax.make_mesh``'s default, refuses those hints)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    The ``pod`` axis extends data parallelism across the DCN: gradient
    reduction crosses pods, everything else stays pod-local.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """All local devices on the data axis (CPU smoke / small runs)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
