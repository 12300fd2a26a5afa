"""Pallas TPU kernel: decode attention over a First-Fit paged KV cache.

The serving-side compute hot-spot of the paper's technique: the page
allocator (``serving/kv_cache.py``) packs sequences into fixed-size HBM
pages (bins); this kernel attends one query token per sequence against its
scattered pages without ever materializing a dense cache.

TPU-native structure:
  - the *page table* and *sequence lengths* are scalar-prefetched
    (``PrefetchScalarGridSpec``) so the BlockSpec index maps can chase the
    page indirection: the K/V block for grid step (b, h, i) is DMA'd from
    HBM page ``page_table[b, i]`` while the previous block computes —
    the TPU version of vLLM's gather;
  - the pool is laid out (num_pages, KVH, page_size, D), so one block is
    one KV head of one page and its last two dimensions are the
    (page_size, D) tile the TPU compiler requires (page_size a multiple of
    8 for f32, 16 for bf16);
  - grid = (B, KVH, max_pages); the page loop is the minor (sequential)
    dimension, so the online-softmax state (m, l, acc) for the G = H/KVH
    grouped query heads lives in VMEM scratch across the sweep;
  - GQA is exploited, not repeated: all G query heads of one KV head are
    processed together as a (G, D) x (D, page_size) MXU matmul;
  - pages past ``ceil(seq_len / page_size)`` are skipped entirely
    (``pl.when``): compute is proportional to the *occupied* bins, exactly
    like the IRM's workers.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention"]

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _paged_attn_kernel(
    page_table_ref,  # scalar-prefetch (B, max_pages) int32
    seq_lens_ref,    # scalar-prefetch (B,) int32
    q_ref,           # (1, 1, G, D)
    k_ref,           # (1, 1, page_size, D)  page pt[b, i], head h
    v_ref,           # (1, 1, page_size, D)
    o_ref,           # (1, 1, G, D)
    m_ref,           # VMEM (G, 1) f32
    l_ref,           # VMEM (G, 1) f32
    acc_ref,         # VMEM (G, D) f32
    *,
    page_size: int,
    n_pages: int,
    scale: float,
):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = seq_lens_ref[b]
    # occupied-bin skip: pages at or past ceil(seq_len / page_size) hold no
    # valid tokens for this sequence
    in_use = (i * page_size) < seq_len

    @pl.when(in_use)
    def _compute():
        q = q_ref[0, 0]        # (G, D)
        k = k_ref[0, 0]        # (page_size, D)
        v = v_ref[0, 0]        # (page_size, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale              # (G, page_size)

        token_pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1
        )
        mask = token_pos < seq_len  # (1, page_size)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(i == n_pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(
    q: jax.Array,           # (B, H, D) one query token per sequence
    k_pool: jax.Array,      # (num_pages, KVH, page_size, D)
    v_pool: jax.Array,      # (num_pages, KVH, page_size, D)
    page_table: jax.Array,  # (B, max_pages) int32, -1 = unused slot
    seq_lens: jax.Array,    # (B,) int32
    *,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    num_pages, KVH, page_size, _ = k_pool.shape
    G = H // KVH
    max_pages = page_table.shape[1]
    scale = 1.0 / math.sqrt(D)

    # unused slots (-1) index page 0; masked out via seq_lens
    table = jnp.maximum(page_table, 0).astype(jnp.int32)
    q_g = q.reshape(B, KVH, G, D)

    kernel = functools.partial(
        _paged_attn_kernel,
        page_size=page_size,
        n_pages=max_pages,
        scale=scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KVH, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, i, pt, sl: (b, h, 0, 0)),
            pl.BlockSpec(
                (1, 1, page_size, D),
                lambda b, h, i, pt, sl: (pt[b, i], h, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, page_size, D),
                lambda b, h, i, pt, sl: (pt[b, i], h, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, G, D), lambda b, h, i, pt, sl: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(table, seq_lens.astype(jnp.int32), q_g, k_pool, v_pool)
    return out.reshape(B, H, D)
