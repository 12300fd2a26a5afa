"""Plain float32 training loss of an OLMo-style decoder over packed rows,
and its gradient.

The model is that of ``reference/olmo.py`` (OLMo, arXiv:2402.00838):
token embedding; per layer a non-parametric LayerNorm, causal multi-head
attention with rotary position embeddings and no biases, a residual add, a
second non-parametric LayerNorm, a SwiGLU MLP and a residual add; a final
non-parametric LayerNorm; logits against the tied embedding table.

A packed row holds several documents, each marked by its segment id
(1, 2, ... in the row; 0 is padding).  From the token ids and segment ids
alone this file derives what training on such a row means:

- positions restart at 0 at each document's first token (RoPE sees
  within-document positions);
- a position attends to itself and the earlier positions of its own
  document, never to another document or to padding;
- each position's target is the next token of its document: there is no
  loss on padding, on a document's last token, or across a boundary.

The loss is the mean next-token cross-entropy over the targets, plus
``z_loss`` times the mean squared log-partition (``lse**2``) over the same
targets; the logits are those of the first ``vocab_size`` rows of the
table.  Its gradient is ``jax.grad``'s.  Both run in float32, every matmul
at ``highest`` precision, a block of rows at a time (``loss_and_grad``),
each layer under ``jax.checkpoint``, so that a block's activations are
those of one layer.  ``fp8=True`` rounds both operands of every matmul to
float8 (e4m3, per-tensor scale; ``reference/gmm.py:fp8``), in the forward
pass and in the backward, whose matmuls take the rounded operands and the
rounded cotangent (the gradient passes straight through the rounding), and
keeps the rest: the control of a cell whose matmuls take bfloat16
operands.  Float8 values are exact in bfloat16, so those matmuls run at
the default precision (one bfloat16 pass with float32 sums on a TPU), the
scales applied to the result.

``adamw_first_update`` is plain AdamW's first step from zero moments: the
change of the weights that a step from the gradient should make.

It imports nothing of the program.  Departures from arXiv:2402.00838:

- the z-loss term: OLMo-1B trains on cross-entropy alone (``z_loss`` 0);
  a cell whose program adds one gives its coefficient;
- a padding position attends to itself alone, so that no softmax row is
  empty; nothing reads its output;
- documents longer than a row arrive as several documents, one a row
  (what the packer makes of them): each chunk's positions restart at 0
  and its last token has no target.

Weights: ``reference/olmo.py``'s dict, layers stacked on the leading axis.
``gather(name, array)``, applied to the table and to each layer's weights
where they are used, lets a caller hold the weights sharded and gather
them a layer at a time (the identity by default).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from reference.olmo import _ln
from reference.olmo import _mm as _mm32

__all__ = ["LAYER_KEYS", "positions", "targets", "attention_mask",
           "objective", "loss_and_grad", "adamw_first_update"]

LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def positions(seg):
    """(B, S) within-document positions: 0 at each document's first token."""
    S = seg.shape[1]
    idx = jnp.arange(S, dtype=jnp.int32)[None, :]
    prev = jnp.pad(seg, ((0, 0), (1, 0)), constant_values=-1)[:, :-1]
    start = jnp.where(seg != prev, idx, 0)
    return idx - jax.lax.cummax(start, axis=1)


def targets(tokens, seg):
    """(B, S) next token of the same document, and where there is one."""
    nxt = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    seg_nxt = jnp.pad(seg[:, 1:], ((0, 0), (0, 1)))
    return nxt, (seg > 0) & (seg_nxt == seg)


def attention_mask(seg):
    """(B, S, S): query q sees key k of its own document with k <= q; a
    padding position sees itself alone."""
    S = seg.shape[1]
    causal = np.tril(np.ones((S, S), bool))
    same = (seg[:, :, None] == seg[:, None, :]) & (seg[:, None, :] > 0)
    return (same & causal[None]) | np.eye(S, dtype=bool)[None]


def _e4m3(x):
    """``x`` as float8 (e4m3) values and a per-tensor scale: their product
    is ``reference/gmm.py:fp8``'s rounding of ``x``."""
    s = jnp.max(jnp.abs(x)) / 224.0
    s = jnp.where(s > 0, s, 1.0)
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3), s


def _mm_e4m3(spec: str, a, b):
    # float8 values are exact in bfloat16, and so are the products of two
    # of them: the default precision's one bfloat16 pass with float32 sums
    # (TPU; float32 elsewhere) gives the float32 product
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.DEFAULT,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm8(spec: str, a, b):
    return _mm8_fwd(spec, a, b)[0]


def _mm8_fwd(spec, a, b):
    (a8, sa), (b8, sb) = _e4m3(a), _e4m3(b)
    return _mm_e4m3(spec, a8, b8) * (sa * sb), (a8, sa, b8, sb)


def _mm8_bwd(spec, res, g):
    # the backward's matmuls take float8 operands too: the rounded
    # operands and the rounded cotangent (straight through the rounding)
    a8, sa, b8, sb = res
    g8, sg = _e4m3(g)
    ins, out = spec.split("->")
    sa_, sb_ = ins.split(",")
    return (_mm_e4m3(f"{out},{sb_}->{sa_}", g8, b8) * (sg * sb),
            _mm_e4m3(f"{sa_},{out}->{sb_}", a8, g8) * (sa * sg))


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def _mm(spec: str, a, b, fp8: bool):
    return _mm8(spec, a, b) if fp8 else _mm32(spec, a, b, False)


def _rope_at(x, pos, theta: float):
    """x: (B, S, H, D); rotate-half RoPE at positions ``pos`` (B, S)."""
    D = x.shape[-1]
    half = D // 2
    inv = jnp.asarray(
        1.0 / (theta ** (np.arange(half, dtype=np.float64) * 2.0 / D)),
        jnp.float32)
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, pos, mask, *, theta: float, fp8: bool):
    """One decoder layer over a block of packed rows x: (B, S, d)."""
    h = _ln(x)
    q = _rope_at(_mm("bsd,dhk->bshk", h, w["wq"], fp8), pos, theta)
    k = _rope_at(_mm("bsd,dhk->bshk", h, w["wk"], fp8), pos, theta)
    v = _mm("bsd,dhk->bshk", h, w["wv"], fp8)
    H, KVH, D = q.shape[2], k.shape[2], q.shape[3]
    k = jnp.repeat(k, H // KVH, axis=2)
    v = jnp.repeat(v, H // KVH, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, fp8) / math.sqrt(D)
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
    a = _mm("bhqk,bkhd->bqhd", p, v, fp8)
    x = x + _mm("bqhd,hde->bqe", a, w["wo"], fp8)
    h = _ln(x)
    g = jax.nn.silu(_mm("bsd,df->bsf", h, w["w_gate"], fp8)) * _mm(
        "bsd,df->bsf", h, w["w_up"], fp8)
    return x + _mm("bsf,fd->bsd", g, w["w_down"], fp8)


def objective(weights: Dict[str, jax.Array], tokens, seg, cfg: Dict, *,
              fp8: bool = False,
              gather: Optional[Callable] = None):
    """Summed over a block of rows: ``(ce + z_loss * z, (ce, z, n))``, with
    ``ce`` the targets' cross-entropy, ``z`` their squared log-partition and
    ``n`` their count."""
    gather = gather or (lambda name, a: a)
    theta, vocab = float(cfg["rope_theta"]), int(cfg["vocab_size"])
    pos, mask = positions(seg), attention_mask(seg)
    table = gather("embed", weights["embed"]).astype(jnp.float32)
    x = table[tokens]

    @jax.checkpoint
    def body(x, w):
        w = {k: gather(k, a) for k, a in w.items()}
        return _layer(x, w, pos, mask, theta=theta, fp8=fp8), None

    x, _ = jax.lax.scan(body, x, {k: weights[k] for k in LAYER_KEYS})
    logits = _mm("bsd,vd->bsv", _ln(x), table[:vocab], fp8)
    lse = jax.nn.logsumexp(logits, axis=-1)
    nxt, valid = targets(tokens, seg)
    picked = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
    ce = jnp.sum(jnp.where(valid, lse - picked, 0.0))
    z = jnp.sum(jnp.where(valid, jnp.square(lse), 0.0))
    n = jnp.sum(valid.astype(jnp.float32))
    return ce + float(cfg["z_loss"]) * z, (ce, z, n)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fp8"))
def _value_and_grad(weights, tokens, seg, *, cfg_items, fp8):
    return jax.value_and_grad(objective, has_aux=True)(
        weights, tokens, seg, dict(cfg_items), fp8=fp8)


def value_and_grad_fn(cfg: Dict, fp8: bool = False):
    """The jitted block function on one device:
    ``(weights, tokens, seg) -> ((total, (ce, z, n)), grads)``."""
    items = tuple(sorted((k, cfg[k]) for k in ("rope_theta", "vocab_size",
                                                 "z_loss")))
    return functools.partial(_value_and_grad, cfg_items=items, fp8=fp8)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, g):
    return jax.tree.map(jnp.add, acc, g)


def loss_and_grad(weights: Dict[str, jax.Array], tokens, seg, cfg: Dict, *,
                  rows_per_block: int, fp8: bool = False,
                  block_fn: Optional[Callable] = None):
    """The mean loss over every target of the rows ``tokens``, ``seg``
    (B, S), and its gradient, a block of ``rows_per_block`` rows at a time.

    ``block_fn(weights, tokens, seg)`` gives a block's
    ``((total, (ce, z, n)), grads)``, by default ``value_and_grad_fn``'s;
    a caller that holds the weights sharded passes its own.  Returns
    ``(loss, ce, grads)``: the loss and its cross-entropy part as floats,
    the gradient as a tree of arrays like ``weights``."""
    block_fn = block_fn or value_and_grad_fn(cfg, fp8)
    tokens, seg = np.asarray(tokens), np.asarray(seg)
    ce = z = n = 0.0
    acc = None
    for lo in range(0, tokens.shape[0], rows_per_block):
        (_, (c, zz, nn)), g = block_fn(weights, tokens[lo:lo + rows_per_block],
                                       seg[lo:lo + rows_per_block])
        ce, z, n = ce + float(c), z + float(zz), n + float(nn)
        acc = g if acc is None else _add(acc, g)
    n = max(n, 1.0)
    loss = (ce + float(cfg["z_loss"]) * z) / n
    return loss, ce / n, jax.tree.map(lambda a: a / n, acc)


def adamw_first_update(weights: Dict[str, jax.Array],
                       grads: Dict[str, jax.Array],
                       opt: Dict) -> Dict[str, jax.Array]:
    """The change of the weights by AdamW's first step (Loshchilov &
    Hutter, with Adam's bias correction) from zero moments, in float32 and
    not yet added to the weights: the gradient clipped to a global L2 norm
    of ``grad_clip_norm``, the learning rate of step 1 of a linear warm-up
    from 0 over ``warmup_steps``, decoupled weight decay on every weight.
    ``opt``: ``learning_rate``, ``b1``, ``b2``, ``eps``, ``weight_decay``,
    ``grad_clip_norm``, ``warmup_steps``."""
    return _adamw_first_update(weights, grads, tuple(sorted(opt.items())))


@functools.partial(jax.jit, static_argnums=(2,))
def _adamw_first_update(weights, grads, opt_items):
    o = dict(opt_items)
    b1, b2, t = o["b1"], o["b2"], 1
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    clip = jnp.minimum(1.0, o["grad_clip_norm"] / norm)
    lr = o["learning_rate"] * min(1.0, t / max(1, o["warmup_steps"]))

    def change(w, g):
        g = g * clip
        m = (1 - b1) * g            # b1 * 0 + (1 - b1) * g
        v = (1 - b2) * g * g
        m_hat, v_hat = m / (1 - b1 ** t), v / (1 - b2 ** t)
        return -lr * (m_hat / (jnp.sqrt(v_hat) + o["eps"])
                      + o["weight_decay"] * w)

    return {k: change(weights[k], grads[k]) for k in weights}
