"""Pallas TPU kernel: flash attention with segment-ID masking.

The compute hot-spot of First-Fit-packed training batches: causal attention
that must not cross the segment boundaries the packer created.  Standard
flash-attention structure adapted to the TPU memory hierarchy:

  - grid (B, H, n_q, n_kv); the minor (last) grid dim executes sequentially
    on a TensorCore, so the online-softmax state (m, l, acc) lives in VMEM
    scratch and survives across the kv sweep;
  - Q/K/V tiles are (block_q x head_dim) / (block_kv x head_dim) VMEM blocks
    with head_dim the 128-lane minor dimension (MXU-aligned);
  - segment ids enter as a (block_q, 1) column for the queries and a
    (1, block_kv) row for the keys, so each block's last two dimensions
    meet the TPU tiling rule and the mask is a plain broadcast compare;
  - logits/softmax accumulate in fp32 on the MXU (bf16 operands);
  - *block skipping*: a (q, kv) tile pair is skipped entirely when causality
    excludes it (kv block strictly above the diagonal).  Segment masking is
    applied within surviving tiles; fully-masked tiles contribute zero
    through the mask (exp(-inf) = 0) without corrupting the running max.

The packing-aware mask is what ties this kernel to the paper: bins = rows,
items = documents, and the kernel is what makes a packed row compute at the
same cost as a dense row (98%+ of tokens are real — see
benchmarks/packing_throughput.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["packed_flash_attention"]

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _attn_kernel(
    seg_q_ref,   # (1, block_q, 1) int32
    seg_kv_ref,  # (1, 1, block_kv) int32
    q_ref,       # (1, 1, block_q, D)
    k_ref,       # (1, 1, block_kv, D)
    v_ref,       # (1, 1, block_kv, D)
    o_ref,       # (1, 1, block_q, D)
    m_ref,       # VMEM (block_q, 1) f32
    l_ref,       # VMEM (block_q, 1) f32
    acc_ref,     # VMEM (block_q, D) f32
    *,
    causal: bool,
    window: int,
    block_q: int,
    block_kv: int,
    n_kv: int,
    scale: float,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    kv_start = ik * block_kv

    # block-level skip: strictly-above-diagonal kv blocks never contribute
    run = True
    if causal:
        run = kv_start <= q_start + block_q - 1
    if window > 0:
        run = jnp.logical_and(run, q_start - (kv_start + block_kv - 1) < window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]  # (bq, D)
        k = k_ref[0, 0]  # (bk, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bq, bk)

        q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kv_ids = kv_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        seg_q = seg_q_ref[0]    # (bq, 1)
        seg_kv = seg_kv_ref[0]  # (1, bk)
        mask = jnp.logical_and(seg_q == seg_kv, seg_kv != 0)
        if causal:
            mask = jnp.logical_and(mask, q_ids >= kv_ids)
        if window > 0:
            mask = jnp.logical_and(mask, q_ids - kv_ids < window)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        # fully-masked rows: s == m_new == NEG_INF would give p = 1
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "interpret"),
)
def packed_flash_attention(
    q: jax.Array,            # (B, H, Sq, D)
    k: jax.Array,            # (B, H, Skv, D)  (KV heads pre-repeated)
    v: jax.Array,            # (B, H, Skv, D)
    segment_ids_q: jax.Array,   # (B, Sq) int32, 0 = padding
    segment_ids_kv: jax.Array,  # (B, Skv)
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 256,
    block_kv: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    if Sq % block_q or Skv % block_kv:
        raise ValueError("sequence lengths must be multiples of the block sizes")
    n_q = Sq // block_q
    n_kv = Skv // block_kv
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _attn_kernel,
        causal=causal,
        window=window,
        block_q=block_q,
        block_kv=block_kv,
        n_kv=n_kv,
        scale=scale,
    )
    grid = (B, H, n_q, n_kv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda b, h, iq, ik: (b, 0, ik)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="packed_attention",
    )(segment_ids_q[:, :, None], segment_ids_kv[:, None, :], q, k, v)
