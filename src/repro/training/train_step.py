"""Training step builder: mixed precision, microbatching, grad compression.

``make_train_step`` returns a pure ``(params, opt_state, batch) -> (params,
opt_state, metrics)`` function suitable for ``jax.jit`` with shardings.

Distributed-optimization features (all optional, all off by default for the
paper-faithful baseline; see EXPERIMENTS.md §Perf for their effect):
  - ``microbatches > 1``: gradient accumulation over a ``lax.scan``; under
    the XLA latency-hiding scheduler the per-microbatch reduce-scatter of
    the previous slice overlaps the next slice's compute.
  - ``compress_grads``: int8-quantized gradient reduction with error
    feedback (``distributed/compression.py``).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .optimizer import OptimizerConfig, adamw_update

__all__ = ["make_train_step", "cast_params_for_compute"]

Pytree = Any


def cast_params_for_compute(params: Pytree, dtype=jnp.bfloat16) -> Pytree:
    """Cast >=2D float params to bf16 for compute; keep vectors in fp32.

    Master params stay fp32 in the optimizer; autodiff through the cast
    produces fp32 gradients automatically.
    """

    def cast(p: jax.Array) -> jax.Array:
        if p.ndim >= 2 and jnp.issubdtype(p.dtype, jnp.floating):
            return p.astype(dtype)
        return p

    return jax.tree.map(cast, params)


def _microbatch_split(batch: Pytree, n: int) -> Pytree:
    """(B, ...) -> (n, B/n, ...) for every leaf."""

    def split(x: jax.Array) -> jax.Array:
        B = x.shape[0]
        if B % n:
            raise ValueError(f"batch dim {B} not divisible by {n} microbatches")
        return x.reshape((n, B // n) + x.shape[1:])

    return jax.tree.map(split, batch)


def make_train_step(
    model: Any,
    opt_cfg: OptimizerConfig,
    *,
    remat_policy: Optional[str] = "nothing",
    microbatches: int = 1,
    compute_dtype=jnp.bfloat16,
    compressor: Optional[Any] = None,
    grad_shardings: Optional[Pytree] = None,
    grad_reduce_dtype: str = "bf16",
) -> Callable[[Pytree, Dict[str, Any], Pytree], Tuple[Pytree, Dict[str, Any], Dict]]:
    """Build the train step for a model with a ``.loss(params, batch)``.

    ``grad_shardings`` (same tree as params) pins the gradients to the
    parameter shardings right at the autodiff output.  Under SPMD this
    pushes the cross-batch-shard gradient combine toward a reduce-scatter
    into the FSDP shards instead of a full all-reduce.

    ``grad_reduce_dtype="bf16"`` differentiates *through the bf16 compute
    params* (the fp32 master cast happens outside autodiff), so the
    per-layer cross-shard gradient reduction moves bf16 on the wire — half
    the bytes of the fp32 reduce (EXPERIMENTS.md §Perf it.3).  The fp32
    conversion for the optimizer happens after the reduce; Adam moments and
    master params stay fp32.  ``"f32"`` keeps the paper-faithful baseline
    behaviour (cast inside autodiff, fp32 reduce).
    """

    def loss_fn(params: Pytree, batch: Pytree) -> Tuple[jax.Array, Dict]:
        return model.loss(params, batch, remat_policy=remat_policy)

    def loss_fn_master(params: Pytree, batch: Pytree) -> Tuple[jax.Array, Dict]:
        compute_params = cast_params_for_compute(params, compute_dtype)
        return model.loss(compute_params, batch, remat_policy=remat_policy)

    bf16_reduce = grad_reduce_dtype == "bf16"
    grad_fn = jax.value_and_grad(
        loss_fn if bf16_reduce else loss_fn_master, has_aux=True
    )

    def compute_grads(params: Pytree, batch: Pytree):
        if bf16_reduce:
            cp = cast_params_for_compute(params, compute_dtype)
            if grad_shardings is not None:
                # pin the bf16 copy to the parameter shardings AND force it
                # to materialize (optimization_barrier): the ZeRO weight
                # all-gathers then move bf16 shards, not fp32 masters with
                # a fused convert (halves AG wire — §Perf it.4; costs one
                # sharded bf16 copy ≈ params/2N bytes of HBM per device)
                cp = jax.tree.map(
                    jax.lax.with_sharding_constraint, cp, grad_shardings
                )
                cp = jax.lax.optimization_barrier(cp)
            out, grads = grad_fn(cp, batch)
        else:
            out, grads = grad_fn(params, batch)
        if grad_shardings is not None:
            grads = jax.tree.map(
                jax.lax.with_sharding_constraint, grads, grad_shardings
            )
        return out, grads

    def train_step(
        params: Pytree, opt_state: Dict[str, Any], batch: Pytree
    ) -> Tuple[Pytree, Dict[str, Any], Dict[str, jax.Array]]:
        if microbatches > 1:
            micro = _microbatch_split(batch, microbatches)

            def body(carry, mb):
                gsum, lsum = carry
                (loss, _), grads = compute_grads(params, mb)
                gsum = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), gsum, grads
                )
                return (gsum, lsum + loss), None

            gzero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (gsum, lsum), _ = lax.scan(
                body, (gzero, jnp.zeros((), jnp.float32)), micro
            )
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
            loss = lsum / microbatches
            metrics: Dict[str, jax.Array] = {"loss": loss}
        else:
            (loss, metrics), grads = compute_grads(params, batch)

        ef_state = opt_state.get("ef")
        opt_core = {k: v for k, v in opt_state.items() if k != "ef"}
        if compressor is not None:
            grads, ef_state = compressor.apply(grads, ef_state)

        with jax.named_scope("optimizer"):
            params_new, opt_new, opt_metrics = adamw_update(
                params, grads, opt_core, opt_cfg
            )
        if ef_state is not None:
            opt_new["ef"] = ef_state
        metrics = dict(metrics, **opt_metrics)
        return params_new, opt_new, metrics

    return train_step
