"""Jit'd public wrapper for packed attention.

Accepts model-layout tensors (B, S, H, D) with separate KV heads, handles
GQA repetition and layout transposes, and runs the Pallas kernel (compiled on
a TPU, interpreted only when asked; see ``kernels.dispatch``) or the jnp
reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..dispatch import pallas_interpret
from .kernel import packed_flash_attention
from .ref import packed_attention_ref

__all__ = ["packed_attention"]


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "use_kernel", "interpret")
)
def packed_attention(
    q: jax.Array,            # (B, Sq, H, D)
    k: jax.Array,            # (B, Skv, KVH, D)
    v: jax.Array,            # (B, Skv, KVH, D)
    segment_ids_q: jax.Array,
    segment_ids_kv: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    use_kernel: bool = True,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, D = q.shape
    KVH = k.shape[2]
    rep = H // KVH
    kf = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vf = jnp.repeat(v, rep, axis=2) if rep > 1 else v

    # pad sequences to block multiples; segment id 0 masks the padding
    block = 256
    pq = (-Sq) % min(block, Sq) if Sq >= block else (-Sq) % 128
    pkv_len = kf.shape[1]
    pkv = (-pkv_len) % min(block, pkv_len) if pkv_len >= block else (-pkv_len) % 128

    def pad_seq(x, p):
        if p == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[1] = (0, p)
        return jnp.pad(x, widths)

    qp, sp_q = pad_seq(q, pq), pad_seq(segment_ids_q, pq)
    kp, vp, sp_kv = pad_seq(kf, pkv), pad_seq(vf, pkv), pad_seq(segment_ids_kv, pkv)

    qt = qp.transpose(0, 2, 1, 3)
    kt = kp.transpose(0, 2, 1, 3)
    vt = vp.transpose(0, 2, 1, 3)
    if use_kernel:
        out = packed_flash_attention(
            qt, kt, vt, sp_q, sp_kv,
            causal=causal, window=window,
            interpret=pallas_interpret(interpret),
        )
    else:
        out = packed_attention_ref(
            qt, kt, vt, sp_q, sp_kv, causal=causal, window=window,
        )
    return out.transpose(0, 2, 1, 3)[:, :Sq]
