"""Pallas TPU kernel: expert-blocked grouped matmul over capacity bins.

The MoE dispatch (``models/moe.py``) packs routed tokens into per-expert
capacity bins — the paper's bin-packing applied to experts.  The expert FFN
is then E independent GEMMs ``(C, d) @ (d, f)`` whose *occupied* row count
varies per expert (``group_sizes``).  This kernel:

  - tiles each expert GEMM into MXU-aligned (block_c x block_d x block_f)
    VMEM blocks; the contraction (d) loop is the minor grid dimension so the
    fp32 accumulator tile lives in VMEM scratch across it;
  - scalar-prefetches ``group_sizes`` and *skips every block* whose row
    range lies past the expert's occupancy (``pl.when``) — compute scales
    with the bins' fill level, not their capacity, exactly like the IRM's
    workers (an empty capacity slot costs nothing);
  - zeroes skipped output tiles so padding rows stay exactly 0 (matching
    the dispatch scatter's zeros and the ref oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul"]


def _gmm_kernel(
    group_sizes_ref,  # scalar-prefetch (E,) int32
    x_ref,            # (1, block_c, block_d)
    w_ref,            # (1, block_d, block_f)
    o_ref,            # (1, block_c, block_f)
    acc_ref,          # VMEM (block_c, block_f) f32
    *,
    block_c: int,
    n_d: int,
):
    e = pl.program_id(0)
    ic = pl.program_id(1)
    kd = pl.program_id(3)

    occupied = (ic * block_c) < group_sizes_ref[e]

    @pl.when(kd == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(occupied)
    def _compute():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kd == n_d - 1)
    def _finalize():
        # zero rows past the expert's occupancy (partial last block)
        rows = ic * block_c + jax.lax.broadcasted_iota(
            jnp.int32, (block_c, 1), 0
        )
        valid = rows < group_sizes_ref[e]
        o_ref[0] = jnp.where(valid, acc_ref[...], 0.0).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_c", "block_d", "block_f", "interpret"),
)
def grouped_matmul(
    x: jax.Array,            # (E, C, d)
    w: jax.Array,            # (E, d, f)
    group_sizes: jax.Array,  # (E,) int32
    *,
    block_c: int = 128,
    block_d: int = 512,
    block_f: int = 128,
    interpret: bool = False,
) -> jax.Array:
    E, C, d = x.shape
    f = w.shape[2]
    block_c = min(block_c, C)
    block_d = min(block_d, d)
    block_f = min(block_f, f)
    if C % block_c or d % block_d or f % block_f:
        raise ValueError(
            f"(C={C}, d={d}, f={f}) must be divisible by blocks "
            f"({block_c}, {block_d}, {block_f})"
        )
    n_c, n_d, n_f = C // block_c, d // block_d, f // block_f

    kernel = functools.partial(_gmm_kernel, block_c=block_c, n_d=n_d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        # contraction (d) minor so the accumulator survives across it
        grid=(E, n_c, n_f, n_d),
        in_specs=[
            pl.BlockSpec(
                (1, block_c, block_d), lambda e, ic, jf, kd, gs: (e, ic, kd)
            ),
            pl.BlockSpec(
                (1, block_d, block_f), lambda e, ic, jf, kd, gs: (e, kd, jf)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, block_c, block_f), lambda e, ic, jf, kd, gs: (e, ic, jf)
        ),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, C, f), x.dtype),
        interpret=interpret,
        name="grouped_matmul",
    )(group_sizes.astype(jnp.int32), x, w)
