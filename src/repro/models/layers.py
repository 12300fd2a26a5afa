"""Core transformer layers: norms, RoPE, chunked flash attention, MLP.

The attention here is the pure-jnp *chunked online-softmax* (flash) form:
peak memory is O(chunk^2) instead of O(S^2), it supports the segment-ID
masks produced by the First-Fit sequence packer (``data/packing.py``), GQA,
sliding windows, and decode against a KV cache.  It is the XLA-partitionable
reference path used by the dry-run; ``kernels/packed_attention`` is the
Pallas TPU version validated against it.

Conventions:
  q: (B, S, H, D)   k/v: (B, S, KVH, D)   segment_ids: (B, S) int32, 0 = pad
  positions: (B, S) int32 — *within-segment* positions (used for RoPE);
  causality uses absolute sequence indices, so packed segments stay causal.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..distributed.context import constrain
from .params import Spec

__all__ = [
    "rms_norm",
    "layer_norm",
    "norm",
    "norm_specs",
    "rope",
    "attention_specs",
    "attention",
    "decode_attention",
    "mlp_specs",
    "mlp",
    "KVCache",
]

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: Optional[jax.Array], eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * lax.rsqrt(var + eps)
    if scale is not None:
        y = y * (1.0 + scale.astype(jnp.float32))
    return y.astype(dtype)


def layer_norm(
    x: jax.Array,
    scale: Optional[jax.Array],
    bias: Optional[jax.Array],
    eps: float = 1e-5,
) -> jax.Array:
    """LayerNorm; with scale=bias=None this is OLMo's non-parametric LN."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dtype)


def norm_specs(norm_type: str, d: int) -> Dict[str, Spec]:
    if norm_type == "rmsnorm":
        return {"scale": Spec((d,), ("embed",), init="zeros")}
    if norm_type == "layernorm":
        return {
            "scale": Spec((d,), ("embed",), init="ones"),
            "bias": Spec((d,), ("embed",), init="zeros"),
        }
    if norm_type == "layernorm_np":  # non-parametric (OLMo)
        return {}
    raise ValueError(f"unknown norm type {norm_type!r}")


def norm(params: Dict[str, jax.Array], norm_type: str, x: jax.Array) -> jax.Array:
    if norm_type == "rmsnorm":
        return rms_norm(x, params["scale"])
    if norm_type == "layernorm":
        return layer_norm(x, params["scale"], params["bias"])
    if norm_type == "layernorm_np":
        return layer_norm(x, None, None)
    raise ValueError(f"unknown norm type {norm_type!r}")


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope(
    x: jax.Array, positions: jax.Array, theta: float = 10000.0
) -> jax.Array:
    """Apply RoPE.  x: (B, S, H, D), positions: (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked flash attention (jnp reference; XLA-partitionable)
# ---------------------------------------------------------------------------


def _mask_chunk(
    q_idx: jax.Array,     # (cq,) absolute indices
    kv_idx: jax.Array,    # (ck,)
    seg_q: jax.Array,     # (B, cq)
    seg_kv: jax.Array,    # (B, ck)
    causal: bool,
    window: int,
) -> jax.Array:
    """(B, cq, ck) bool mask: segment match & causality & sliding window."""
    m = (seg_q[:, :, None] == seg_kv[:, None, :]) & (seg_kv[:, None, :] != 0)
    if causal:
        m &= q_idx[None, :, None] >= kv_idx[None, None, :]
    if window > 0:
        m &= (q_idx[None, :, None] - kv_idx[None, None, :]) < window
    return m


def _flash_q_chunk(
    q: jax.Array,        # (B, cq, H, D) fp32 compute
    k: jax.Array,        # (B, S, H, D) (KV heads pre-repeated to H)
    v: jax.Array,        # (B, S, H, D)
    q_idx: jax.Array,    # (cq,)
    seg_q: jax.Array,    # (B, cq)
    seg_kv: jax.Array,   # (B, S)
    *,
    causal: bool,
    window: int,
    chunk_kv: int,
    scale: float,
    with_lse: bool = False,
):
    """One query chunk's output (B, cq, H, D); with ``with_lse`` also each
    query's log-sum-exp of its scores (B, H, cq), which the backward
    recomputes the chunk's probabilities from."""
    B, cq, H, D = q.shape
    S = k.shape[1]
    n_kv = S // chunk_kv

    k = k.reshape(B, n_kv, chunk_kv, H, D)
    v = v.reshape(B, n_kv, chunk_kv, H, D)
    seg_kv = seg_kv.reshape(B, n_kv, chunk_kv)
    kv_idx = jnp.arange(S, dtype=jnp.int32).reshape(n_kv, chunk_kv)

    def step(carry, xs):
        m_run, l_run, acc = carry
        k_c, v_c, seg_c, idx_c = xs
        # logits: (B, H, cq, ck) — H stays sharded over the model axis
        # bf16 operands, fp32 accumulation (MXU-native flash numerics)
        s = jnp.einsum(
            "bqhd,bkhd->bhqk",
            q,
            k_c,
            preferred_element_type=jnp.float32,
        ) * scale
        mask = _mask_chunk(q_idx, idx_c, seg_q, seg_c, causal, window)
        s = jnp.where(mask[:, None, :, :], s, _NEG_INF)
        m_new = jnp.maximum(m_run, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        # fully-masked rows: s == m_new == NEG_INF would give p = 1; zero
        # them so padded query positions produce exactly 0 (matches the
        # Pallas kernel and the dense oracle).
        p = jnp.where(mask[:, None, :, :], p, 0.0)
        alpha = jnp.exp(m_run - m_new)
        l_new = alpha * l_run + p.sum(axis=-1)
        acc_new = alpha[..., None] * acc + jnp.einsum(
            "bhqk,bkhd->bhqd",
            p.astype(v_c.dtype),
            v_c,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, cq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, cq), jnp.float32)
    a0 = jnp.zeros((B, H, cq, D), jnp.float32)
    (m_f, l_f, acc), _ = lax.scan(
        step,
        (m0, l0, a0),
        (
            jnp.moveaxis(k, 1, 0),
            jnp.moveaxis(v, 1, 0),
            jnp.moveaxis(seg_kv, 1, 0),
            kv_idx,
        ),
    )
    out = acc / jnp.maximum(l_f[..., None], 1e-30)
    out = jnp.moveaxis(out, -2, 1)  # (B, cq, H, D)
    if with_lse:
        return out, m_f + jnp.log(jnp.maximum(l_f, 1e-30))
    return out


class _FlashOpts(NamedTuple):
    causal: bool
    window: int
    chunk_q: int
    chunk_kv: int
    q_offset: int
    scale: float


def _flash_forward(qp, kp, vp, sq, skv, o: _FlashOpts, with_lse: bool):
    """Padded (B, Sq_p, H, D) inputs; the output (and the log-sum-exp,
    (n_q, B, H, cq)) a query chunk at a time."""
    B, Sq_p, H, D = qp.shape
    n_q = Sq_p // o.chunk_q
    qp = qp.reshape(B, n_q, o.chunk_q, H, D)
    sq_c = sq.reshape(B, n_q, o.chunk_q)
    q_idx = (
        jnp.arange(Sq_p, dtype=jnp.int32).reshape(n_q, o.chunk_q) + o.q_offset
    )

    def one_chunk(xs):
        q_c, seg_c, idx_c = xs
        return _flash_q_chunk(
            q_c, kp, vp, idx_c, seg_c, skv,
            causal=o.causal, window=o.window, chunk_kv=o.chunk_kv,
            scale=o.scale, with_lse=with_lse,
        )

    out = lax.map(
        one_chunk, (jnp.moveaxis(qp, 1, 0), jnp.moveaxis(sq_c, 1, 0), q_idx)
    )  # (n_q, B, cq, H, D)
    if with_lse:
        out, lse = out
        return jnp.moveaxis(out, 0, 1).reshape(B, Sq_p, H, D), lse
    return jnp.moveaxis(out, 0, 1).reshape(B, Sq_p, H, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _flash(qp, kp, vp, sq, skv, o: _FlashOpts):
    return _flash_forward(qp, kp, vp, sq, skv, o, with_lse=False)


def _flash_fwd(qp, kp, vp, sq, skv, o):
    out, lse = _flash_forward(qp, kp, vp, sq, skv, o, with_lse=True)
    return out, (qp, kp, vp, sq, skv, out, lse)


def _flash_bwd(o: _FlashOpts, res, g):
    """FlashAttention-2's backward: for each (query, key) block pair the
    probabilities come back from the scores and the saved log-sum-exp;
    dV += P^T dO, dS = P * (dO V^T - rowsum(dO * O)), dQ += dS K,
    dK += dS^T Q.  Holds one block's scores at a time and the float32
    dK, dV accumulators."""
    qp, kp, vp, sq, skv, out, lse = res
    B, Sq_p, H, D = qp.shape
    Skv = kp.shape[1]
    cq, ck = o.chunk_q, o.chunk_kv
    n_q, n_kv = Sq_p // cq, Skv // ck
    g = g.astype(jnp.float32)
    delta = jnp.einsum("bshd,bshd->bhs", g, out)
    delta = jnp.moveaxis(delta.reshape(B, H, n_q, cq), 2, 0)  # (n_q, B, H, cq)

    def chunks(x, c):  # (B, S, ...) -> (S / c, B, c, ...)
        return jnp.moveaxis(x.reshape(B, x.shape[1] // c, c, *x.shape[2:]),
                            1, 0)

    q_idx = jnp.arange(Sq_p, dtype=jnp.int32).reshape(n_q, cq) + o.q_offset
    kv_idx = jnp.arange(Skv, dtype=jnp.int32).reshape(n_kv, ck)

    def q_step(carry, xs):
        dk, dv = carry  # (n_kv, B, ck, H, D) float32
        q_c, g_c, lse_c, delta_c, sq_c, qi = xs
        g_v = g_c.astype(vp.dtype)

        def kv_step(dq, ys):
            k_c, v_c, dk_c, dv_c, sk_c, ki = ys
            s = jnp.einsum("bqhd,bkhd->bhqk", q_c, k_c,
                           preferred_element_type=jnp.float32) * o.scale
            mask = _mask_chunk(qi, ki, sq_c, sk_c, o.causal, o.window)
            p = jnp.where(mask[:, None], jnp.exp(s - lse_c[..., None]), 0.0)
            dv_c = dv_c + jnp.einsum("bhqk,bqhd->bkhd", p.astype(vp.dtype),
                                     g_v, preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqhd,bkhd->bhqk", g_v, v_c,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta_c[..., None]) * o.scale
            dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds.astype(kp.dtype), k_c,
                                 preferred_element_type=jnp.float32)
            dk_c = dk_c + jnp.einsum("bhqk,bqhd->bkhd", ds.astype(qp.dtype),
                                     q_c, preferred_element_type=jnp.float32)
            return dq, (dk_c, dv_c)

        dq, (dk, dv) = lax.scan(
            kv_step, jnp.zeros((B, cq, H, D), jnp.float32),
            (chunks(kp, ck), chunks(vp, ck), dk, dv, chunks(skv, ck), kv_idx),
        )
        return (dk, dv), dq

    zeros = jnp.zeros((n_kv, B, ck, H, D), jnp.float32)
    (dk, dv), dq = lax.scan(
        q_step, (zeros, zeros),
        (chunks(qp, cq), chunks(g, cq), lse, delta, chunks(sq, cq), q_idx),
    )

    def whole(x, like):  # (n, B, c, H, D) -> like's (B, S, H, D)
        return jnp.moveaxis(x, 0, 1).reshape(like.shape).astype(like.dtype)

    return whole(dq, qp), whole(dk, kp), whole(dv, vp), None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, KVH, D) -> (B, S, KVH*n_rep, D).

    For GQA under tensor parallelism the repeat is a no-comm *split* of the
    (replicated) KV heads onto the model-sharded H axis — this keeps the
    attention logits sharded over heads even when KVH < mesh model size
    (the un-repeated grouped einsum forces XLA to replicate the logits,
    measured at +3.2 GB all-reduce per layer on qwen2-72b train_4k).
    """
    if n_rep == 1:
        return k
    B, S, KVH, D = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (B, S, KVH, n_rep, D))
    return k.reshape(B, S, KVH * n_rep, D)


def flash_attention(
    q: jax.Array,            # (B, Sq, H, D)
    k: jax.Array,            # (B, Skv, KVH, D)
    v: jax.Array,            # (B, Skv, KVH, D)
    segment_ids_q: jax.Array,   # (B, Sq)
    segment_ids_kv: jax.Array,  # (B, Skv)
    *,
    causal: bool = True,
    window: int = 0,
    chunk_q: int = 512,
    chunk_kv: int = 512,
    q_offset: int = 0,
) -> jax.Array:
    """Chunked online-softmax attention with segment masking.  O(c^2) memory
    in the forward and in the backward, which recomputes each block's
    probabilities from the saved log-sum-exp (FlashAttention-2) instead of
    keeping every block's scores."""
    B, Sq, H, D = q.shape
    KVH = k.shape[2]
    scale = 1.0 / math.sqrt(D)

    k = constrain(repeat_kv(k, H // KVH), ("batch", None, "heads", None))
    v = constrain(repeat_kv(v, H // KVH), ("batch", None, "heads", None))

    chunk_q = min(chunk_q, Sq)
    chunk_kv = min(chunk_kv, k.shape[1])
    # pad Sq/Skv to chunk multiples (segment id 0 == masked padding)
    def pad_to(x, c, axis):
        rem = (-x.shape[axis]) % c
        if rem == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, rem)
        return jnp.pad(x, widths)

    qp = pad_to(q, chunk_q, 1)
    kp = pad_to(k, chunk_kv, 1)
    vp = pad_to(v, chunk_kv, 1)
    sq = pad_to(segment_ids_q, chunk_q, 1)
    skv = pad_to(segment_ids_kv, chunk_kv, 1)

    out = _flash(qp, kp, vp, sq, skv,
                 _FlashOpts(causal, window, chunk_q, chunk_kv, q_offset, scale))
    return out[:, :Sq].astype(q.dtype)


def _decode_attention_local(
    q: jax.Array,          # (B, 1, H, D)
    k_cache: jax.Array,    # (B, S_local, KVH, D)
    v_cache: jax.Array,
    cache_len: jax.Array,  # (B,)
    offset,                # global index of this shard's first cache slot
    axes: Tuple[str, ...],  # collective axes ((),) = single device
    *,
    window: int,
) -> jax.Array:
    """Flash-decode shard body: local partial softmax + tiny cross-shard
    combine (pmax of the max, psum of denominator/numerator)."""
    B, _, H, D = q.shape
    S, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    scale = 1.0 / math.sqrt(D)
    qf = q.reshape(B, KVH, G, D).astype(jnp.float32)
    s = jnp.einsum(
        "bhgd,bkhd->bhgk", qf, k_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale                                           # (B, KVH, G, S)
    idx = offset + jnp.arange(S, dtype=jnp.int32)[None, :]  # (1, S) global
    cache_len = jnp.asarray(cache_len).reshape(-1, 1)
    valid = idx < cache_len
    if window > 0:
        valid &= idx >= (cache_len - window)
    s = jnp.where(valid[:, None, None, :], s, _NEG_INF)

    m = s.max(axis=-1)                                   # (B, KVH, G)
    for ax in axes:
        m = lax.pmax(m, ax)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum(
        "bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    if axes:
        l = lax.psum(l, axes)
        acc = lax.psum(acc, axes)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, 1, H, D).astype(q.dtype)


def decode_attention_distributed(
    q: jax.Array,          # (B, 1, H, D) — batch over data, repl. over model
    k_cache: jax.Array,    # (B, S, KVH, D) — S sharded over the model axis
    v_cache: jax.Array,
    cache_len: jax.Array,  # (B,)
    *,
    window: int = 0,
) -> Optional[jax.Array]:
    """Distributed flash-decode over a sequence-sharded KV cache.

    GQA KV-head counts are usually smaller than the model axis (qwen2: 8
    heads vs 16 shards), so the decode cache shards over the *sequence*
    dim.  Plain attention over that layout forces XLA to gather the cache
    or the logits every layer (measured 9.1 GB/step/device on qwen2-72b
    decode_32k).  This shard_map computes each shard's partial online
    softmax locally and combines with a pmax+2 psums of (B, H)-sized
    tensors — ~1 MB/layer (EXPERIMENTS.md §Perf).

    Returns None when no mesh context is active or the layout doesn't
    shard the cache sequence (callers fall back to the dense path).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..distributed.context import _STATE  # same-module convention

    ctx = getattr(_STATE, "ctx", None)
    if ctx is None:
        return None
    mesh, rules = ctx
    from ..distributed.sharding import axes_to_pspec

    B, S = k_cache.shape[0], k_cache.shape[1]
    kv_spec = axes_to_pspec(
        ("batch", "kv_seq", "kv_heads", None), k_cache.shape, rules, mesh
    )
    seq_entry = kv_spec[1]
    if seq_entry is None:
        return None  # cache not sequence-sharded: dense path is fine
    seq_axes = seq_entry if isinstance(seq_entry, tuple) else (seq_entry,)
    batch_entry = kv_spec[0]

    n_shards = 1
    for ax in seq_axes:
        n_shards *= mesh.shape[ax]
    s_local = S // n_shards

    def body(q_l, k_l, v_l, len_l):
        # global offset of this shard's slice (row-major over seq_axes)
        offset = jnp.zeros((), jnp.int32)
        for ax in seq_axes:
            offset = offset * mesh.shape[ax] + lax.axis_index(ax)
        offset = offset * s_local
        return _decode_attention_local(
            q_l, k_l, v_l, len_l, offset, tuple(seq_axes), window=window
        )

    q_spec = P(batch_entry, None, None, None)
    len_spec = P(batch_entry)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, len_spec),
        out_specs=q_spec,
        check_vma=False,
    )(q, k_cache, v_cache, jnp.asarray(cache_len).reshape(B))


def decode_attention(
    q: jax.Array,          # (B, 1, H, D)
    k_cache: jax.Array,    # (B, S, KVH, D)
    v_cache: jax.Array,    # (B, S, KVH, D)
    cache_len: jax.Array,  # (B,) or scalar — number of valid cache entries
    *,
    window: int = 0,
) -> jax.Array:
    """Single-token attention against a dense KV cache (serving decode)."""
    B, _, H, D = q.shape
    S, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    scale = 1.0 / math.sqrt(D)
    qf = q.reshape(B, KVH, G, D).astype(jnp.float32)
    s = jnp.einsum(
        "bhgd,bkhd->bhgk",
        qf,
        k_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale
    idx = jnp.arange(S, dtype=jnp.int32)[None, :]  # (1, S)
    cache_len = jnp.asarray(cache_len).reshape(-1, 1)  # (B or 1, S)
    valid = idx < cache_len
    if window > 0:
        valid &= idx >= (cache_len - window)
    s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhgk,bkhd->bhgd",
        p,
        v_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (projections + flash core)
# ---------------------------------------------------------------------------


def attention_specs(cfg: Any, cross: bool = False) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.head_dim_
    H, KVH = cfg.n_heads, cfg.n_kv_heads
    specs: Dict[str, Any] = {
        "wq": Spec((d, H, hd), ("embed", "heads", "head_dim"), init="scaled",
                   fan_in=d),
        "wk": Spec((d, KVH, hd), ("embed", "kv_heads", "head_dim"),
                   init="scaled", fan_in=d),
        "wv": Spec((d, KVH, hd), ("embed", "kv_heads", "head_dim"),
                   init="scaled", fan_in=d),
        "wo": Spec((H, hd, d), ("heads", "head_dim", "embed"), init="scaled",
                   fan_in=H * hd),
    }
    if cfg.qkv_bias:
        specs["bq"] = Spec((H, hd), ("heads", "head_dim"), init="zeros")
        specs["bk"] = Spec((KVH, hd), ("kv_heads", "head_dim"), init="zeros")
        specs["bv"] = Spec((KVH, hd), ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        specs["q_norm"] = Spec((hd,), ("head_dim",), init="zeros")
        specs["k_norm"] = Spec((hd,), ("head_dim",), init="zeros")
    return specs


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Every layer's dense KV cache, stacked, carried through decode steps."""

    k: jax.Array  # (n_layers, B, S_max, KVH, D)
    v: jax.Array  # (n_layers, B, S_max, KVH, D)


def _project_qkv(
    p: Dict[str, jax.Array], cfg: Any, x: jax.Array, x_kv: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x_kv, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x_kv, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    # TP layout inside the block: heads over model, sequence gathered
    q = constrain(q, ("batch", None, "heads", None))
    k = constrain(k, ("batch", None, "kv_heads", None))
    v = constrain(v, ("batch", None, "kv_heads", None))
    return q, k, v


def attention(
    p: Dict[str, jax.Array],
    cfg: Any,
    x: jax.Array,                 # (B, S, d)
    segment_ids: jax.Array,       # (B, S)
    positions: jax.Array,         # (B, S)
    *,
    causal: bool = True,
    x_kv: Optional[jax.Array] = None,           # cross-attention source
    segment_ids_kv: Optional[jax.Array] = None,
    positions_kv: Optional[jax.Array] = None,
    use_rope: bool = True,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full-sequence attention (train / prefill).  Returns (out, (k, v))."""
    x_kv = x if x_kv is None else x_kv
    segment_ids_kv = segment_ids if segment_ids_kv is None else segment_ids_kv
    positions_kv = positions if positions_kv is None else positions_kv

    q, k, v = _project_qkv(p, cfg, x, x_kv)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions_kv, cfg.rope_theta)
    out = flash_attention(
        q, k, v, segment_ids, segment_ids_kv,
        causal=causal, window=cfg.sliding_window,
    )
    out = constrain(out, ("batch", None, "heads", None))
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, (k, v)


def attention_decode(
    p: Dict[str, jax.Array],
    cfg: Any,
    x: jax.Array,              # (B, 1, d)
    position: jax.Array,       # (B,) within-sequence position of the token
    cache: KVCache,
    layer: jax.Array,          # () index of this layer in the stacked cache
    cache_len: jax.Array,      # (B,) valid entries *including* this token
    *,
    use_rope: bool = True,
) -> Tuple[jax.Array, KVCache]:
    """One decode step of one layer: write the token's K/V into row
    ``cache_len - 1`` of layer ``layer`` of the stacked cache, attend over
    that layer.

    Carried through a ``lax.scan`` over layers, the stack is written in
    place, a few rows a step.  Fed to the scan as ``xs`` and returned as
    ``ys``, each layer's slab would be copied out, and the whole stack
    back, every step."""
    B = x.shape[0]
    q, k, v = _project_qkv(p, cfg, x, x)
    if use_rope:
        pos = position.reshape(B, 1)
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    write_idx = (cache_len - 1).astype(jnp.int32)  # (B,)
    b_idx = jnp.arange(B, dtype=jnp.int32)
    k_all = cache.k.at[layer, b_idx, write_idx].set(k[:, 0].astype(cache.k.dtype))
    v_all = cache.v.at[layer, b_idx, write_idx].set(v[:, 0].astype(cache.v.dtype))
    k_cache, v_cache = k_all[layer], v_all[layer]
    # distributed flash-decode when the cache is sequence-sharded under the
    # active mesh; dense path otherwise (single device, tests)
    out = decode_attention_distributed(
        q, k_cache, v_cache, cache_len, window=cfg.sliding_window
    )
    if out is None:
        out = decode_attention(
            q, k_cache, v_cache, cache_len, window=cfg.sliding_window
        )
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, KVCache(k=k_all, v=v_all)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_specs(cfg: Any, d_ff: Optional[int] = None) -> Dict[str, Spec]:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    if cfg.act == "swiglu":
        return {
            "w_gate": Spec((d, f), ("embed", "mlp"), init="scaled"),
            "w_up": Spec((d, f), ("embed", "mlp"), init="scaled"),
            "w_down": Spec((f, d), ("mlp", "embed"), init="scaled"),
        }
    return {
        "w_up": Spec((d, f), ("embed", "mlp"), init="scaled"),
        "w_down": Spec((f, d), ("mlp", "embed"), init="scaled"),
    }


def mlp(p: Dict[str, jax.Array], cfg: Any, x: jax.Array) -> jax.Array:
    if cfg.act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = jax.nn.gelu(x @ p["w_up"])
    h = constrain(h, ("batch", None, "mlp"))
    return h @ p["w_down"]
