"""Decoder-only LM assembled from an ``ArchConfig``.

The layer stack is ``lax.scan``'d over *periods* of the (possibly
heterogeneous) ``layer_pattern`` — e.g. jamba's ``MMMMAMMM`` — with the
pattern unrolled inside the scan body and per-position parameters stacked
over periods.  This keeps the HLO size O(period) regardless of depth (95
layers compile as 1 scanned period body), which is what makes the 512-device
dry-run of the large configs tractable.  ``decode_step`` alone unrolls its
scan, trading compile time that grows with depth for reading each weight
once a step (see there).

Three entry points, matching the assigned input shapes:
  - ``loss``        : training forward + chunked cross-entropy (train_4k)
  - ``prefill``     : full-sequence forward building the KV/state caches
                      (prefill_32k)
  - ``decode_step`` : one new token against the caches (decode_32k,
                      long_500k)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..distributed.context import constrain
from . import moe as moe_lib
from . import ssm as ssm_lib
from . import xlstm as xlstm_lib
from .layers import (
    KVCache,
    attention,
    attention_decode,
    attention_specs,
    mlp,
    mlp_specs,
    norm,
    norm_specs,
)
from .params import Spec

__all__ = ["DecoderLM", "chunked_cross_entropy", "pad_vocab"]


def pad_vocab(v: int, multiple: int = 256) -> int:
    """Pad vocab to a multiple of 256 so it shards over any mesh axis."""
    return ((v + multiple - 1) // multiple) * multiple


# ---------------------------------------------------------------------------
# Chunked cross-entropy (never materializes (B, S, V) logits)
# ---------------------------------------------------------------------------


def chunked_cross_entropy(
    hidden: jax.Array,      # (B, S, d)
    table: jax.Array,       # (V, d) embedding/unembedding table
    labels: jax.Array,      # (B, S) int32, -1 = masked
    *,
    chunk: int = 512,
    z_loss: float = 1e-4,
    vocab: Optional[int] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Mean next-token cross-entropy over the positions whose label is not
    -1, plus ``z_loss`` times the mean squared log-partition.  ``vocab``:
    the model's vocabulary, when the table has rows past it (``pad_vocab``);
    those rows are no token, and their logits are left out of the softmax."""
    B, S, d = hidden.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    n = hidden.shape[1] // chunk
    hidden = hidden.reshape(B, n, chunk, d)
    labels = labels.reshape(B, n, chunk)

    @jax.checkpoint
    def chunk_loss(h_c: jax.Array, l_c: jax.Array):
        # batch over the data axes ONLY so the vocab dim can take "model":
        # the (b, chunk, V) logits then stay fully sharded and the only
        # cross-shard work is the tiny (b, chunk) logsumexp combine —
        # vs ~15 GB/step of replicated-logit all-reduce otherwise (§Perf).
        h_c = constrain(h_c, ("batch_data", None, None))
        logits = jnp.einsum(
            "bsd,vd->bsv", h_c.astype(jnp.float32), table.astype(jnp.float32)
        )
        logits = constrain(logits, ("batch_data", None, "vocab"))
        if vocab is not None and vocab < table.shape[0]:
            real = jnp.arange(table.shape[0]) < vocab
            logits = jnp.where(real, logits, -jnp.inf)
        lse = jax.nn.logsumexp(logits, axis=-1)
        idx = jnp.maximum(l_c, 0)
        picked = jnp.take_along_axis(logits, idx[..., None], axis=-1)[..., 0]
        valid = (l_c >= 0).astype(jnp.float32)
        ce = (lse - picked) * valid
        zl = jnp.square(lse) * valid
        return ce.sum(), zl.sum(), valid.sum()

    def body(carry, xs):
        ce_s, zl_s, n_s = carry
        h_c, l_c = xs
        ce, zl, nv = chunk_loss(h_c, l_c)
        return (ce_s + ce, zl_s + zl, n_s + nv), None

    (ce_sum, zl_sum, n_valid), _ = lax.scan(
        body,
        (jnp.zeros((), jnp.float32),) * 3,
        (jnp.moveaxis(hidden, 1, 0), jnp.moveaxis(labels, 1, 0)),
    )
    n_valid = jnp.maximum(n_valid, 1.0)
    loss = ce_sum / n_valid + z_loss * zl_sum / n_valid
    return loss, {"ce": ce_sum / n_valid, "tokens": n_valid}


# ---------------------------------------------------------------------------
# Block spec / apply dispatch table
# ---------------------------------------------------------------------------


def _block_specs(cfg: Any, pos: int) -> Dict[str, Any]:
    """Parameter specs for the block at position ``pos`` within the period."""
    char = cfg.pattern[pos]
    specs: Dict[str, Any] = {"ln1": norm_specs(cfg.norm_type, cfg.d_model)}
    if char == "A":
        specs["mixer"] = attention_specs(cfg)
    elif char == "M":
        specs["mixer"] = ssm_lib.mamba_specs(cfg)
    elif char == "l":
        specs["mixer"] = xlstm_lib.mlstm_specs(cfg)
    elif char == "s":
        specs["mixer"] = xlstm_lib.slstm_specs(cfg)
    else:
        raise ValueError(f"unknown pattern char {char!r}")
    if char in ("A", "M") and (cfg.d_ff or cfg.moe):
        specs["ln2"] = norm_specs(cfg.norm_type, cfg.d_model)
        if cfg.moe is not None and cfg.moe.is_moe_layer(pos):
            specs["ffn"] = moe_lib.moe_specs(cfg)
        elif cfg.d_ff:
            specs["ffn"] = mlp_specs(cfg)
    return specs


def _stack_period(cfg: Any, spec_tree: Any) -> Any:
    """Prepend the scanned 'layers' (periods) dimension to every spec."""
    n = cfg.n_periods

    def stack(s: Spec) -> Spec:
        return dataclasses.replace(
            s, shape=(n,) + s.shape, axes=("layers",) + s.axes
        )

    return jax.tree.map(stack, spec_tree, is_leaf=lambda x: isinstance(x, Spec))


def _zero_aux() -> Dict[str, jax.Array]:
    z = jnp.zeros((), jnp.float32)
    return {"moe_load_balance": z, "moe_z_loss": z, "moe_drop_fraction": z}


def _add_aux(a: Dict[str, jax.Array], b: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return {k: a[k] + b.get(k, 0.0) for k in a}


# ---------------------------------------------------------------------------
# DecoderLM
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DecoderLM:
    cfg: Any

    # ---- parameters ---------------------------------------------------------
    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        v = pad_vocab(cfg.vocab_size)
        specs: Dict[str, Any] = {
            # unit-variance embeddings for untied models: every block starts
            # with a norm, so N(0,1) rows keep rsqrt(var) ~ 1 and the embed
            # gradient on the same scale as the rest (0.02-scale init +
            # rms_norm amplifies the embed grad ~2500x).  Tied models keep
            # the small init — the same table is the unembed projection.
            "embed": Spec((v, cfg.d_model), ("vocab", "embed"), init="normal",
                          scale=0.02 if cfg.tie_embeddings else 1.0),
            "final_norm": norm_specs(cfg.norm_type, cfg.d_model),
            "blocks": {
                str(pos): _stack_period(cfg, _block_specs(cfg, pos))
                for pos in range(len(cfg.pattern))
            },
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = Spec(
                (v, cfg.d_model), ("vocab", "embed"), init="scaled"
            )
        return specs

    def _table(self, params: Dict[str, Any]) -> jax.Array:
        return params["embed"] if self.cfg.tie_embeddings else params["lm_head"]

    # ---- embedding ----------------------------------------------------------
    def _embed(self, params: Dict[str, Any], batch: Dict[str, jax.Array]) -> jax.Array:
        cfg = self.cfg
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
        if cfg.frontend == "vision" and "vision_embeds" in batch:
            # frontend stub: precomputed patch embeddings fill the prefix
            nv = batch["vision_embeds"].shape[1]
            x = x.at[:, :nv].set(batch["vision_embeds"].astype(x.dtype))
        return x

    # ---- block application ----------------------------------------------------
    def _apply_block_train(
        self,
        char: str,
        p: Dict[str, Any],
        cfg: Any,
        x: jax.Array,
        seg: jax.Array,
        pos_ids: jax.Array,
        aux: Dict[str, jax.Array],
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        x = constrain(x, ("batch", "seq", None))
        with jax.named_scope("norm"):
            h = norm(p["ln1"], cfg.norm_type, x)
        if char == "A":
            with jax.named_scope("attention"):
                out, _ = attention(p["mixer"], cfg, h, seg, pos_ids)
        elif char == "M":
            out, _ = ssm_lib.mamba_forward(p["mixer"], cfg, h)
        elif char == "l":
            out, _ = xlstm_lib.mlstm_forward(p["mixer"], cfg, h)
        else:
            out, _ = xlstm_lib.slstm_forward(p["mixer"], cfg, h)
        x = x + constrain(out, ("batch", "seq", None))
        if "ffn" in p:
            with jax.named_scope("norm"):
                h = norm(p["ln2"], cfg.norm_type, x)
            with jax.named_scope("mlp"):
                if "router" in p["ffn"]:
                    out, moe_aux = moe_lib.moe_layer(p["ffn"], cfg, h)
                    aux = _add_aux(aux, moe_aux)
                else:
                    out = mlp(p["ffn"], cfg, h)
            x = x + constrain(out, ("batch", "seq", None))
        return x, aux

    # ---- training forward -----------------------------------------------------
    def hidden_states(
        self,
        params: Dict[str, Any],
        batch: Dict[str, jax.Array],
        *,
        remat_policy: Optional[str] = "nothing",
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = self._embed(params, batch)
        seg = batch["segment_ids"]
        pos_ids = batch["positions"]

        def period_body(carry, period_params):
            x, aux = carry
            for pos, char in enumerate(cfg.pattern):
                x, aux = self._apply_block_train(
                    char, period_params[str(pos)], cfg, x, seg, pos_ids, aux
                )
            return (x, aux), None

        if remat_policy is not None:
            period_body = _remat(period_body, remat_policy)

        with jax.named_scope("layers"):
            (x, aux), _ = lax.scan(period_body, (x, _zero_aux()),
                                   params["blocks"])
        with jax.named_scope("norm"):
            x = norm(params["final_norm"], cfg.norm_type, x)
        return x, aux

    def loss(
        self,
        params: Dict[str, Any],
        batch: Dict[str, jax.Array],
        *,
        remat_policy: Optional[str] = "nothing",
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        x, aux = self.hidden_states(params, batch, remat_policy=remat_policy)
        with jax.named_scope("loss"):
            loss, metrics = chunked_cross_entropy(
                x, self._table(params), batch["labels"],
                vocab=self.cfg.vocab_size,
            )
        loss = loss + aux["moe_load_balance"] + aux["moe_z_loss"]
        metrics = dict(metrics, **aux, loss=loss)
        return loss, metrics

    # ---- serving: prefill -------------------------------------------------------
    def prefill(
        self, params: Dict[str, Any], batch: Dict[str, jax.Array], *,
        max_len: int,
    ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Returns (last-token logits (B, V), cache pytree).

        The K/V caches hold ``max_len`` positions, the prompt's first, as
        ``init_cache(B, max_len)`` lays them out: decode writes past the
        end of an array would be dropped."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = self._embed(params, batch)
        seg = batch["segment_ids"]
        pos_ids = batch["positions"]
        B, S = seg.shape
        if max_len < S:
            raise ValueError(f"max_len {max_len} < prompt length {S}")
        room = [(0, 0), (0, max_len - S), (0, 0), (0, 0)]  # (B, S, KVH, D)

        def period_body(x, period_params):
            caches = {}
            for pos, char in enumerate(cfg.pattern):
                p = period_params[str(pos)]
                x = constrain(x, ("batch", "seq", None))
                with jax.named_scope("norm"):
                    h = norm(p["ln1"], cfg.norm_type, x)
                if char == "A":
                    with jax.named_scope("attention"):
                        out, (k, v) = attention(p["mixer"], cfg, h, seg, pos_ids)
                        caches[str(pos)] = {"k": jnp.pad(k, room),
                                            "v": jnp.pad(v, room)}
                elif char == "M":
                    out, st = ssm_lib.mamba_forward(p["mixer"], cfg, h)
                    caches[str(pos)] = st
                elif char == "l":
                    out, st = xlstm_lib.mlstm_forward(p["mixer"], cfg, h)
                    caches[str(pos)] = st
                else:
                    out, st = xlstm_lib.slstm_forward(p["mixer"], cfg, h)
                    caches[str(pos)] = st
                x = x + out
                if "ffn" in p:
                    with jax.named_scope("norm"):
                        h = norm(p["ln2"], cfg.norm_type, x)
                    with jax.named_scope("mlp"):
                        if "router" in p["ffn"]:
                            out, _ = moe_lib.moe_layer(p["ffn"], cfg, h)
                        else:
                            out = mlp(p["ffn"], cfg, h)
                    x = x + out
            return x, caches

        with jax.named_scope("layers"):
            x, caches = lax.scan(period_body, x, params["blocks"])
        with jax.named_scope("norm"):
            x = norm(params["final_norm"], cfg.norm_type, x)
        with jax.named_scope("logits"):
            # last valid position per row
            last = jnp.maximum(jnp.sum((seg > 0).astype(jnp.int32), axis=1) - 1, 0)
            x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
            logits = x_last.astype(jnp.float32) @ self._table(params).T.astype(
                jnp.float32
            )
        cache = {
            "blocks": caches,
            "len": jnp.sum((seg > 0).astype(jnp.int32), axis=1),
        }
        return logits, cache

    # ---- serving: decode ---------------------------------------------------------
    def decode_step(
        self,
        params: Dict[str, Any],
        batch: Dict[str, jax.Array],  # {"tokens": (B, 1)}
        cache: Dict[str, Any],
    ) -> Tuple[jax.Array, Dict[str, Any]]:
        """One token for every sequence in the batch.  Cache is donated."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], batch["tokens"], axis=0)  # (B, 1, d)
        new_len = cache["len"] + 1  # includes the new token
        position = cache["len"]     # 0-based position of the new token
        # attention caches ride in the carry, written in place; the
        # recurrent blocks' small states go through the scan as xs / ys
        blocks = cache["blocks"]
        kv = {str(pos): blocks[str(pos)]
              for pos, char in enumerate(cfg.pattern) if char == "A"}
        states = {pos: c for pos, c in blocks.items() if pos not in kv}
        recurrent = {"M": ssm_lib.mamba_decode_step,
                     "l": xlstm_lib.mlstm_decode_step,
                     "s": xlstm_lib.slstm_decode_step}

        def period_body(carry, xs):
            x, kv = carry
            period_params, i, period_states = xs
            new_states = {}
            for pos, char in enumerate(cfg.pattern):
                key = str(pos)
                p = period_params[key]
                x = constrain(x, ("batch", None, None))
                with jax.named_scope("norm"):
                    h = norm(p["ln1"], cfg.norm_type, x)
                if char == "A":
                    with jax.named_scope("attention"):
                        out, c = attention_decode(
                            p["mixer"], cfg, h, position, KVCache(**kv[key]),
                            i, new_len,
                        )
                    kv[key] = {"k": c.k, "v": c.v}
                else:
                    out, new_states[key] = recurrent[char](
                        p["mixer"], cfg, h, period_states[key])
                x = x + out
                if "ffn" in p:
                    with jax.named_scope("norm"):
                        h = norm(p["ln2"], cfg.norm_type, x)
                    with jax.named_scope("mlp"):
                        if "router" in p["ffn"]:
                            out, _ = moe_lib.moe_layer(p["ffn"], cfg, h)
                        else:
                            out = mlp(p["ffn"], cfg, h)
                    x = x + out
            return (x, kv), new_states

        # unrolled: a step uses each weight once, and over a rolled loop the
        # compiler rounds every whole weight stack to the matmuls' operand
        # type before the loop, writing a copy it then reads back; with
        # static slices each weight's rounding fuses into its matmul
        with jax.named_scope("layers"):
            (x, kv), states = lax.scan(
                period_body, (x, kv),
                (params["blocks"], jnp.arange(cfg.n_periods), states),
                unroll=True,
            )
        with jax.named_scope("norm"):
            x = norm(params["final_norm"], cfg.norm_type, x)
        with jax.named_scope("logits"):
            logits = x[:, 0].astype(jnp.float32) @ self._table(params).T.astype(
                jnp.float32
            )
        return logits, {"blocks": {**states, **kv}, "len": new_len}

    # ---- cache allocation ----------------------------------------------------------
    def init_cache(
        self, batch_size: int, max_len: int, dtype: Any = jnp.bfloat16
    ) -> Dict[str, Any]:
        """Dense cache pytree (used to build dry-run ShapeDtypeStructs too)."""
        cfg = self.cfg
        n = cfg.n_periods
        blocks: Dict[str, Any] = {}
        for pos, char in enumerate(cfg.pattern):
            if char == "A":
                kv_shape = (n, batch_size, max_len, cfg.n_kv_heads, cfg.head_dim_)
                blocks[str(pos)] = {
                    "k": jnp.zeros(kv_shape, dtype),
                    "v": jnp.zeros(kv_shape, dtype),
                }
            elif char == "M":
                st = ssm_lib.mamba_init_state(cfg, batch_size)
                blocks[str(pos)] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (n,) + a.shape), st
                )
            elif char == "l":
                st = xlstm_lib.mlstm_init_state(cfg, batch_size)
                blocks[str(pos)] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (n,) + a.shape), st
                )
            else:
                st = xlstm_lib.slstm_init_state(cfg, batch_size)
                blocks[str(pos)] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (n,) + a.shape), st
                )
        return {
            "blocks": blocks,
            "len": jnp.zeros((batch_size,), jnp.int32),
        }


def _remat(fn, policy: str):
    policies = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "everything": jax.checkpoint_policies.everything_saveable,
    }
    return jax.checkpoint(fn, policy=policies[policy], prevent_cse=False)
