"""Pure-jnp oracle for paged decode attention.

Gathers each sequence's pages into a dense KV view and runs masked decode
attention — the semantics the Pallas kernel must reproduce exactly.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["paged_attention_ref", "gather_pages"]


def gather_pages(
    pool: jax.Array,        # (num_pages, KVH, page_size, D)
    page_table: jax.Array,  # (B, max_pages) int32, -1 = unused
) -> jax.Array:
    """Dense (B, max_pages * page_size, KVH, D) view of the paged cache.

    Unused table slots (-1) gather page 0; the caller masks by seq_lens, so
    the garbage never contributes.
    """
    idx = jnp.maximum(page_table, 0)                       # (B, P)
    gathered = pool[idx]                                   # (B, P, KVH, ps, D)
    B, P, KVH, ps, D = gathered.shape
    return gathered.transpose(0, 1, 3, 2, 4).reshape(B, P * ps, KVH, D)


def paged_attention_ref(
    q: jax.Array,           # (B, H, D) one query token per sequence
    k_pool: jax.Array,      # (num_pages, KVH, page_size, D)
    v_pool: jax.Array,      # (num_pages, KVH, page_size, D)
    page_table: jax.Array,  # (B, max_pages) int32, -1 = unused
    seq_lens: jax.Array,    # (B,) valid tokens per sequence
) -> jax.Array:
    B, H, D = q.shape
    KVH = k_pool.shape[1]
    G = H // KVH
    scale = 1.0 / math.sqrt(D)

    k = gather_pages(k_pool, page_table).astype(jnp.float32)  # (B, S, KVH, D)
    v = gather_pages(v_pool, page_table).astype(jnp.float32)
    S = k.shape[1]

    qf = q.reshape(B, KVH, G, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, k) * scale           # (B, KVH, G, S)
    valid = jnp.arange(S)[None, :] < seq_lens[:, None]          # (B, S)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v)
    return out.reshape(B, H, D).astype(q.dtype)
