"""Operations and bytes that the work needs, counted from its shapes.

These are the algorithm's needs, not what a given implementation moves:
a kernel that reads an operand twice does not count it twice, and causal
attention counts the positions a query may see.  So a share of a
roofline or of a peak computed from them cannot pass 100 %.
"""

from __future__ import annotations

from typing import Dict

__all__ = ["gmm_call", "decoder_matmul_params", "prefill_flops",
           "decode_step_flops", "decode_step_bytes"]


def gmm_call(experts: int, rows: int, dim: int, out_dim: int,
             itemsize: int = 4) -> Dict[str, float]:
    """One grouped matmul ``(E, C, d) x (E, d, f)`` with every row valid:
    its FLOPs, and the bytes of reading both operands and writing the
    output once."""
    flops = 2.0 * experts * rows * dim * out_dim
    nbytes = itemsize * (experts * rows * dim + experts * dim * out_dim
                         + experts * rows * out_dim)
    return {"flops": flops, "bytes": float(nbytes)}


def decoder_matmul_params(m: Dict) -> Dict[str, float]:
    """Weights that each token multiplies, per layer and in the output
    projection, for a dense decoder with SwiGLU (``m``: ArchConfig fields)."""
    d, H, KVH = m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // H
    per_layer = d * H * hd + 2 * d * KVH * hd + H * hd * d + 3 * d * m["d_ff"]
    return {"per_layer": float(per_layer),
            "layers": float(per_layer * m["n_layers"]),
            "unembed": float(d * m["vocab_size"])}


def _attn_per_query(m: Dict, context: float) -> float:
    """QK^T and PV for one query over ``context`` positions, all layers."""
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    return 4.0 * m["n_layers"] * m["n_heads"] * hd * context


def prefill_flops(m: Dict, batch: int, prompt_len: int) -> float:
    """A prefill of ``batch`` prompts: every position through every layer,
    causal attention, and the logits of the last position."""
    p = decoder_matmul_params(m)
    tokens = batch * prompt_len
    attn = batch * _attn_per_query(m, 1.0) * prompt_len * (prompt_len + 1) / 2
    return 2.0 * tokens * p["layers"] + attn + 2.0 * batch * p["unembed"]


def decode_step_flops(m: Dict, batch: int, context: int) -> float:
    """One decode step of ``batch`` sequences whose new token sees
    ``context`` positions (itself included)."""
    p = decoder_matmul_params(m)
    return batch * (2.0 * (p["layers"] + p["unembed"])
                    + _attn_per_query(m, context))


def decode_step_bytes(m: Dict, batch: int, context: int,
                      weight_itemsize: int = 4,
                      cache_itemsize: int = 4) -> float:
    """One decode step reads every weight it multiplies once and the K and
    V of the ``context`` valid positions of each sequence."""
    p = decoder_matmul_params(m)
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    kv = 2.0 * m["n_layers"] * m["n_kv_heads"] * hd * context * batch
    return weight_itemsize * (p["layers"] + p["unembed"]) + cache_itemsize * kv
