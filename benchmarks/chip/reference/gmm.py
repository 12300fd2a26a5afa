"""Plain float32 grouped matmul, and its lower-precision control.

``out[e, c] = x[e, c] @ w[e]`` for the first ``sizes[e]`` rows of each
group, zero past them.  It imports nothing of the program.  The float32
reference runs at ``highest`` precision.  The control computes the same
product with both operands rounded to float8 (e4m3, a per-tensor scale
taking the largest magnitude to 224) and float32 accumulation: the step
below the bfloat16 passes that the kernel's float32 operands take on the
MXU at the default precision.  The rounding is ``reduce_precision``, which
XLA keeps; a float32 -> float8 -> float32 ``convert`` pair is one that XLA
may drop as excess precision, and on the chip it did.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["fp8", "grouped_matmul", "grouped_matmul_fp8"]


def fp8(x):
    """``x`` rounded to float8 e4m3 under a per-tensor scale."""
    s = jnp.max(jnp.abs(x)) / 224.0
    s = jnp.where(s > 0, s, 1.0)
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3) * s


def _gmm(x, w, sizes):
    out = jnp.einsum("ecd,edf->ecf", x, w,
                     precision=jax.lax.Precision.HIGHEST)
    rows = jnp.arange(x.shape[1])[None, :, None]
    return jnp.where(rows < sizes[:, None, None], out, 0.0)


@jax.jit
def grouped_matmul(x, w, sizes):
    return _gmm(x.astype(jnp.float32), w.astype(jnp.float32), sizes)


@jax.jit
def grouped_matmul_fp8(x, w, sizes):
    return _gmm(fp8(x.astype(jnp.float32)), fp8(w.astype(jnp.float32)), sizes)
