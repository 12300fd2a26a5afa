"""Jit'd public wrapper for paged decode attention.

Bridges the host-side ``PageAllocator`` (First-Fit page tables as numpy) and
the device kernel, and runs the kernel (compiled on a TPU, interpreted only
when asked; see ``kernels.dispatch``) or the jnp reference.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp

from ...serving.kv_cache import PageAllocator
from ..dispatch import pallas_interpret
from .kernel import paged_decode_attention
from .ref import paged_attention_ref

__all__ = ["paged_attention", "page_table_from_allocator"]


def page_table_from_allocator(
    allocator: PageAllocator, seq_ids: List[int]
) -> tuple:
    """(page_table, seq_lens) device arrays for the active sequences."""
    table = jnp.asarray(allocator.page_table(seq_ids), jnp.int32)
    lens = jnp.asarray(
        [allocator.seq_len(s) for s in seq_ids], jnp.int32
    )
    return table, lens


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def paged_attention(
    q: jax.Array,           # (B, H, D)
    k_pool: jax.Array,      # (num_pages, KVH, page_size, D)
    v_pool: jax.Array,      # (num_pages, KVH, page_size, D)
    page_table: jax.Array,  # (B, max_pages) int32, -1 = unused
    seq_lens: jax.Array,    # (B,)
    *,
    use_kernel: bool = True,
    interpret: bool = False,
) -> jax.Array:
    if use_kernel:
        return paged_decode_attention(
            q, k_pool, v_pool, page_table, seq_lens,
            interpret=pallas_interpret(interpret),
        )
    return paged_attention_ref(q, k_pool, v_pool, page_table, seq_lens)
