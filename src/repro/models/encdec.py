"""Encoder-decoder LM (seamless-m4t backbone).

The audio frontend is a stub per the assignment: ``input_specs()`` provides
precomputed frame embeddings for the encoder (B, S_enc, d).  The decoder is a
standard causal stack with cross-attention to the encoder output; decode
shapes lower the *decoder* step with the encoder output (and cross K/V)
cached.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .layers import (
    KVCache,
    attention,
    attention_decode,
    attention_specs,
    decode_attention,
    mlp,
    mlp_specs,
    norm,
    norm_specs,
    _project_qkv,
)
from ..distributed.context import constrain
from .params import Spec
from .transformer import _remat, chunked_cross_entropy, pad_vocab

__all__ = ["EncDecLM"]


@dataclasses.dataclass
class EncDecLM:
    cfg: Any

    # ---- parameters -----------------------------------------------------------
    def _enc_layer_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        return {
            "ln1": norm_specs(cfg.norm_type, cfg.d_model),
            "self_attn": attention_specs(cfg),
            "ln2": norm_specs(cfg.norm_type, cfg.d_model),
            "ffn": mlp_specs(cfg),
        }

    def _dec_layer_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        return {
            "ln1": norm_specs(cfg.norm_type, cfg.d_model),
            "self_attn": attention_specs(cfg),
            "ln_cross": norm_specs(cfg.norm_type, cfg.d_model),
            "cross_attn": attention_specs(cfg),
            "ln2": norm_specs(cfg.norm_type, cfg.d_model),
            "ffn": mlp_specs(cfg),
        }

    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        v = pad_vocab(cfg.vocab_size)

        def stack(n: int, tree: Any) -> Any:
            return jax.tree.map(
                lambda s: dataclasses.replace(
                    s, shape=(n,) + s.shape, axes=("layers",) + s.axes),
                tree,
                is_leaf=lambda x: isinstance(x, Spec),
            )

        return {
            # unit-variance embeddings (see transformer.py rationale)
            "embed": Spec((v, cfg.d_model), ("vocab", "embed"), init="normal",
                          scale=1.0),
            "enc_blocks": stack(cfg.n_encoder_layers, self._enc_layer_specs()),
            "enc_norm": norm_specs(cfg.norm_type, cfg.d_model),
            "dec_blocks": stack(cfg.n_layers, self._dec_layer_specs()),
            "final_norm": norm_specs(cfg.norm_type, cfg.d_model),
            "lm_head": Spec((v, cfg.d_model), ("vocab", "embed"), init="scaled"),
        }

    # ---- encoder -----------------------------------------------------------------
    def encode(
        self,
        params: Dict[str, Any],
        enc_embeds: jax.Array,       # (B, Se, d) stub frame embeddings
        enc_segment_ids: jax.Array,  # (B, Se)
        *,
        remat_policy: Optional[str] = "nothing",
    ) -> jax.Array:
        cfg = self.cfg
        B, Se, _ = enc_embeds.shape
        pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32)[None], (B, Se))

        def body(x, p):
            x = constrain(x, ("batch", "seq", None))
            h = norm(p["ln1"], cfg.norm_type, x)
            out, _ = attention(
                p["self_attn"], cfg, h, enc_segment_ids, pos, causal=False
            )
            x = x + out
            h = norm(p["ln2"], cfg.norm_type, x)
            return x + mlp(p["ffn"], cfg, h), None

        if remat_policy is not None:
            body = _remat(body, remat_policy)
        x, _ = lax.scan(body, enc_embeds, params["enc_blocks"])
        return norm(params["enc_norm"], cfg.norm_type, x)

    # ---- decoder (training / prefill over full sequence) ---------------------------
    def _decoder_hidden(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,
        segment_ids: jax.Array,
        positions: jax.Array,
        enc_out: jax.Array,
        enc_segment_ids: jax.Array,
        *,
        remat_policy: Optional[str] = "nothing",
        collect_cache: bool = False,
    ):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        B, Se, _ = enc_out.shape
        enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32)[None], (B, Se))

        def body(x, p):
            x = constrain(x, ("batch", "seq", None))
            h = norm(p["ln1"], cfg.norm_type, x)
            out, (k, v) = attention(p["self_attn"], cfg, h, segment_ids, positions)
            x = x + out
            h = norm(p["ln_cross"], cfg.norm_type, x)
            out, (ck, cv) = attention(
                p["cross_attn"], cfg, h, segment_ids, positions,
                causal=False,
                x_kv=enc_out, segment_ids_kv=enc_segment_ids,
                positions_kv=enc_pos, use_rope=False,
            )
            x = x + out
            h = norm(p["ln2"], cfg.norm_type, x)
            x = x + mlp(p["ffn"], cfg, h)
            cache = {"k": k, "v": v, "ck": ck, "cv": cv} if collect_cache else None
            return x, cache

        if remat_policy is not None and not collect_cache:
            body = _remat(body, remat_policy)
        x, caches = lax.scan(body, x, params["dec_blocks"])
        return norm(params["final_norm"], cfg.norm_type, x), caches

    # ---- entry points ------------------------------------------------------------
    def loss(
        self,
        params: Dict[str, Any],
        batch: Dict[str, jax.Array],
        *,
        remat_policy: Optional[str] = "nothing",
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        enc_out = self.encode(
            params, batch["enc_embeds"], batch["enc_segment_ids"],
            remat_policy=remat_policy,
        )
        x, _ = self._decoder_hidden(
            params, batch["tokens"], batch["segment_ids"], batch["positions"],
            enc_out, batch["enc_segment_ids"], remat_policy=remat_policy,
        )
        loss, metrics = chunked_cross_entropy(x, params["lm_head"], batch["labels"])
        return loss, dict(metrics, loss=loss)

    def prefill(
        self, params: Dict[str, Any], batch: Dict[str, jax.Array], *,
        max_len: int,
    ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Returns (last-token logits (B, V), cache pytree) with self-attention
        K/V room for ``max_len`` decoder positions, as ``init_cache`` lays
        them out."""
        S = batch["segment_ids"].shape[1]
        if max_len < S:
            raise ValueError(f"max_len {max_len} < prompt length {S}")
        enc_out = self.encode(
            params, batch["enc_embeds"], batch["enc_segment_ids"],
            remat_policy=None,
        )
        x, caches = self._decoder_hidden(
            params, batch["tokens"], batch["segment_ids"], batch["positions"],
            enc_out, batch["enc_segment_ids"],
            remat_policy=None, collect_cache=True,
        )
        room = [(0, 0), (0, 0), (0, max_len - S), (0, 0), (0, 0)]
        caches = dict(caches, k=jnp.pad(caches["k"], room),
                      v=jnp.pad(caches["v"], room))  # (layers, B, S, KVH, D)
        seg = batch["segment_ids"]
        last = jnp.maximum(jnp.sum((seg > 0).astype(jnp.int32), axis=1) - 1, 0)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = x_last.astype(jnp.float32) @ params["lm_head"].T.astype(jnp.float32)
        cache = {
            "blocks": caches,
            "enc_segment_ids": batch["enc_segment_ids"],
            "len": jnp.sum((seg > 0).astype(jnp.int32), axis=1),
        }
        return logits, cache

    def decode_step(
        self,
        params: Dict[str, Any],
        batch: Dict[str, jax.Array],
        cache: Dict[str, Any],
    ) -> Tuple[jax.Array, Dict[str, Any]]:
        """One decoder token; cross K/V are precomputed in the cache."""
        cfg = self.cfg
        x = jnp.take(params["embed"], batch["tokens"], axis=0)  # (B, 1, d)
        new_len = cache["len"] + 1
        position = cache["len"]
        enc_valid = jnp.sum(
            (cache["enc_segment_ids"] > 0).astype(jnp.int32), axis=1
        )

        blocks = cache["blocks"]

        def body(carry, xs):
            x, kv = carry
            p, cross, i = xs  # cross K/V are read, never written
            x = constrain(x, ("batch", None, None))
            h = norm(p["ln1"], cfg.norm_type, x)
            out, kv = attention_decode(
                p["self_attn"], cfg, h, position, kv, i, new_len,
            )
            x = x + out
            h = norm(p["ln_cross"], cfg.norm_type, x)
            q, _, _ = _project_qkv(p["cross_attn"], cfg, h, h)
            out = decode_attention(q, cross["ck"], cross["cv"], enc_valid)
            out = jnp.einsum("bshk,hkd->bsd", out, p["cross_attn"]["wo"])
            x = x + out
            h = norm(p["ln2"], cfg.norm_type, x)
            x = x + mlp(p["ffn"], cfg, h)
            return (x, kv), None

        (x, kv), _ = lax.scan(
            body, (x, KVCache(k=blocks["k"], v=blocks["v"])),
            (params["dec_blocks"], {"ck": blocks["ck"], "cv": blocks["cv"]},
             jnp.arange(cfg.n_layers)),
        )
        x = norm(params["final_norm"], cfg.norm_type, x)
        logits = x[:, 0].astype(jnp.float32) @ params["lm_head"].T.astype(jnp.float32)
        return logits, {
            "blocks": dict(blocks, k=kv.k, v=kv.v),
            "enc_segment_ids": cache["enc_segment_ids"],
            "len": new_len,
        }

    def init_cache(
        self, batch_size: int, max_len: int, enc_len: int, dtype: Any = jnp.bfloat16
    ) -> Dict[str, Any]:
        cfg = self.cfg
        n = cfg.n_layers
        kvh, hd = cfg.n_kv_heads, cfg.head_dim_
        return {
            "blocks": {
                "k": jnp.zeros((n, batch_size, max_len, kvh, hd), dtype),
                "v": jnp.zeros((n, batch_size, max_len, kvh, hd), dtype),
                "ck": jnp.zeros((n, batch_size, enc_len, kvh, hd), dtype),
                "cv": jnp.zeros((n, batch_size, enc_len, kvh, hd), dtype),
            },
            "enc_segment_ids": jnp.ones((batch_size, enc_len), jnp.int32),
            "len": jnp.zeros((batch_size,), jnp.int32),
        }
