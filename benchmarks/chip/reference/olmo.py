"""Plain float32 ``jax.numpy`` forward of an OLMo-style decoder.

OLMo (arXiv:2402.00838): token embedding; per layer a non-parametric
LayerNorm (eps 1e-5), causal multi-head attention with rotary position
embeddings (rotate-half form, theta from the config) and no biases, a
residual add, a second non-parametric LayerNorm, a SwiGLU MLP
(``silu(h W_gate) * (h W_up)) W_down``) and a residual add; a final
non-parametric LayerNorm; logits against the tied embedding table.
Key/value heads fewer than query heads are shared in groups.

It imports nothing of the program.  It runs one layer at a time, one
sequence at a time, with plain softmax attention over the whole sequence,
so that it fits beside the weights, in float32 at ``highest`` precision.
``fp8=True`` rounds both operands of every matmul to float8 (e4m3, a
per-tensor scale; ``reference/gmm.py:fp8``) and keeps the rest: the
control of a cell whose matmuls run as bfloat16 passes.

Weights: a dict of arrays, layers stacked on the leading axis:
``embed (V, d)``; ``wq (L, d, H, hd)``, ``wk``/``wv (L, d, KVH, hd)``,
``wo (L, H, hd, d)``; ``w_gate``/``w_up (L, d, f)``, ``w_down (L, f, d)``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from reference.gmm import fp8 as _round_fp8

__all__ = ["logits_at", "served_gaps", "gaps_and_control"]

EPS = 1e-5


def _ln(x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + jnp.asarray(EPS, x.dtype))


def _rope(x, theta: float):
    """x: (S, H, D); positions 0..S-1."""
    S, _, D = x.shape
    half = D // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) * 2.0 / D))
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), x.dtype)[:, None, :]
    sin = jnp.asarray(np.sin(ang), x.dtype)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(spec: str, a, b, fp8: bool):
    if fp8:
        a, b = _round_fp8(a), _round_fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("theta", "fp8"))
def _layer(x, wq, wk, wv, wo, wg, wu, wd, *, theta: float, fp8: bool):
    """One decoder layer over one sequence x: (S, d)."""
    S = x.shape[0]
    h = _ln(x)
    q = _rope(_mm("sd,dhk->shk", h, wq, fp8), theta)
    k = _rope(_mm("sd,dhk->shk", h, wk, fp8), theta)
    v = _mm("sd,dhk->shk", h, wv, fp8)
    H, KVH, D = q.shape[1], k.shape[1], q.shape[2]
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)
    s = _mm("qhd,khd->hqk", q, k, fp8) / math.sqrt(D)
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = _mm("hqk,khd->qhd", p, v, fp8)
    x = x + _mm("qhd,hde->qe", a, wo, fp8)
    h = _ln(x)
    g = jax.nn.silu(_mm("sd,df->sf", h, wg, fp8)) * _mm("sd,df->sf", h, wu,
                                                          fp8)
    return x + _mm("sf,fd->sd", g, wd, fp8)


@functools.partial(jax.jit, static_argnames=("fp8",))
def _logits(x, table, *, fp8: bool):
    return _mm("sd,vd->sv", _ln(x), table, fp8)


def logits_at(weights: Dict[str, jax.Array], cfg: Dict, tokens, start: int,
              vocab: int, fp8: bool = False) -> np.ndarray:
    """Logits over the first ``vocab`` ids at positions ``start..S-1`` of
    one sequence of token ids."""
    x = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(weights["wq"].shape[0]):
        x = _layer(x, *(weights[k][i] for k in (
            "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")),
            theta=float(cfg["rope_theta"]), fp8=fp8)
    return np.asarray(_logits(x[start:], weights["embed"][:vocab], fp8=fp8))


def _teacher_forced(prompt, served):
    """The sequence the reference reads, and where its predictions start:
    position ``len(prompt) - 1 + t`` predicts served token ``t``."""
    return np.concatenate([prompt, served[:-1]]), len(prompt) - 1


def _gaps(ref: np.ndarray, served) -> np.ndarray:
    served = np.asarray(served)
    ok = (served >= 0) & (served < ref.shape[1])
    picked = np.take_along_axis(ref, np.where(ok, served, 0)[:, None], 1)[:, 0]
    return np.where(ok, ref.max(axis=1) - picked, np.inf)


def served_gaps(weights, cfg, prompt, served, vocab: int) -> np.ndarray:
    """Per served token, how far its float32 reference logit lies below the
    reference's best (0 where the program chose the reference's best; inf
    for a token id outside the vocabulary)."""
    seq, start = _teacher_forced(prompt, served)
    return _gaps(logits_at(weights, cfg, seq, start, vocab), served)


def gaps_and_control(weights, cfg, prompt, served, vocab: int):
    """``served_gaps``, and the control's: at each position of the same
    prompt and served tokens, the gap below the float32 reference's best of
    the token that the float8-matmul forward puts first.  The reference is
    read once for both."""
    seq, start = _teacher_forced(prompt, served)
    ref = logits_at(weights, cfg, seq, start, vocab)
    low = logits_at(weights, cfg, seq, start, vocab, fp8=True)
    return _gaps(ref, served), _gaps(ref, low.argmax(axis=1))
