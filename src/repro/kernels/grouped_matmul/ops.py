"""Jit'd public wrapper for the grouped matmul: runs the kernel (compiled on
a TPU, interpreted only when asked; see ``kernels.dispatch``) or the jnp
reference, and provides the fused SwiGLU expert-FFN built from three grouped
GEMMs."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..dispatch import pallas_interpret
from .kernel import grouped_matmul
from .ref import grouped_matmul_ref

__all__ = ["gmm", "expert_ffn_swiglu"]


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def gmm(
    x: jax.Array,            # (E, C, d)
    w: jax.Array,            # (E, d, f)
    group_sizes: jax.Array,  # (E,)
    *,
    use_kernel: bool = True,
    interpret: bool = False,
) -> jax.Array:
    if use_kernel:
        return grouped_matmul(
            x, w, group_sizes, interpret=pallas_interpret(interpret)
        )
    return grouped_matmul_ref(x, w, group_sizes)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def expert_ffn_swiglu(
    x: jax.Array,            # (E, C, d) capacity-packed tokens
    w_gate: jax.Array,       # (E, d, f)
    w_up: jax.Array,         # (E, d, f)
    w_down: jax.Array,       # (E, f, d)
    group_sizes: jax.Array,  # (E,)
    *,
    use_kernel: bool = True,
    interpret: bool = False,
) -> jax.Array:
    kw = dict(use_kernel=use_kernel, interpret=interpret)
    h = jax.nn.silu(gmm(x, w_gate, group_sizes, **kw)) * gmm(
        x, w_up, group_sizes, **kw
    )
    return gmm(h, w_down, group_sizes, **kw)
