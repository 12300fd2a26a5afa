"""Model FLOPs of a training step over packed rows, counted from shapes.

A real (non-padding) token costs 6 FLOPs per weight it multiplies: 2 in
the forward pass and 4 in the backward (the gradients of the activation
and of the weight), the tied output projection included.  Causal
attention within each document costs, per (query, key) pair it may see,
4 FLOPs per head dimension per head per layer forward (QK^T and PV), and
three times that with the backward.  Recomputation (remat), padding and
the masked parts of a packed row's score matrix are not counted, so a
share of the peak computed from it cannot pass 100 %.
"""

from __future__ import annotations

from typing import Dict, Iterable

from flops import decoder_matmul_params

__all__ = ["train_flops"]


def train_flops(m: Dict, doc_lengths: Iterable[int]) -> float:
    """One step over documents of these lengths (each a document, or the
    part of one, that a packed row holds)."""
    lengths = [int(n) for n in doc_lengths]
    p = decoder_matmul_params(m)
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    pairs = sum(n * (n + 1) / 2 for n in lengths)
    attn_pair = 4.0 * m["n_layers"] * m["n_heads"] * hd
    return (6.0 * sum(lengths) * (p["layers"] + p["unembed"])
            + 3.0 * attn_pair * pairs)
