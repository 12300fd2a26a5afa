"""Production serving driver.

Runs the IRM-scheduled continuous-batching engine against either the
discrete-time simulated backend (capacity planning / control-plane soak,
``--backend sim``) or a real model executing prefill + decode on the local
device (``--backend local``: a TPU at full width, or a reduced config on a
CPU with ``--smoke``).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --backend sim --requests 500
  PYTHONPATH=src python -m repro.launch.serve --backend local \
      --arch qwen3-8b --smoke --requests 8
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Dict

import numpy as np

from ..configs import ARCH_NAMES, get_config
from ..serving import EngineConfig, ReplicaConfig, Request, ServingEngine
from .compile_cache import enable_compilation_cache


def run_sim(args: argparse.Namespace) -> None:
    cfg = EngineConfig(
        replica=ReplicaConfig(
            max_slots=args.slots, kv_pages=args.pages,
            prefill_tokens_per_s=100_000.0, decode_tokens_per_s=8_000.0,
            spinup_delay=5.0,
        ),
        max_replicas=args.replicas,
        dt=0.1,
    )
    eng = ServingEngine(cfg)
    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        eng.submit(Request(prompt_len=int(rng.integers(128, 2048)),
                           max_new_tokens=int(rng.integers(32, 512))))
    eng.run_until_drained(t_max=3600.0)
    s = eng.summary()
    print(f"completed {s['completed']}/{args.requests}  "
          f"makespan {s['makespan']:.1f}s  p50 {s['p50_latency']:.2f}s  "
          f"p99 {s['p99_latency']:.2f}s  peak replicas {s['peak_replicas']}")


def run_local(args: argparse.Namespace) -> Dict[str, Any]:
    """Prefill a batch of 16-token prompts, then decode greedily on the
    local device.

    Prints the compile seconds (timed apart from the run), the prefill
    seconds and the decode tokens per second, each labelled with the
    device.  Returns the prefill logits, the generated tokens (B, 1 + gen)
    and each decode step's logits (B, gen, V), with the parameters and
    batch they came from (for a reference to compare with).
    """
    import jax
    import jax.numpy as jnp

    from ..models import build_model, init_params

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    model = build_model(cfg)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    B = min(args.requests, 8)
    prompt_len, gen = 16, args.gen_tokens
    prompts = jnp.asarray(
        rng.integers(1, cfg.vocab_size, size=(B, prompt_len)), jnp.int32
    )
    batch = {
        "tokens": prompts,
        "segment_ids": jnp.ones((B, prompt_len), jnp.int32),
        "positions": jnp.broadcast_to(
            jnp.arange(prompt_len, dtype=jnp.int32), (B, prompt_len)
        ),
    }
    if cfg.encdec:
        batch["enc_embeds"] = jnp.asarray(
            rng.normal(size=(B, prompt_len, cfg.d_model)) * 0.02, jnp.float32)
        batch["enc_segment_ids"] = jnp.ones((B, prompt_len), jnp.int32)
    if cfg.frontend == "vision":
        batch["vision_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.frontend_tokens, cfg.d_model)) * 0.02,
            jnp.float32)

    prefill = jax.jit(
        lambda p, b: model.prefill(p, b, max_len=prompt_len + gen))
    decode = jax.jit(model.decode_step, donate_argnums=(2,))

    t0 = time.perf_counter()
    prefill_c = prefill.lower(params, batch).compile()
    _, cache_shape = jax.eval_shape(prefill, params, batch)
    tok_shape = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    decode_c = decode.lower(params, {"tokens": tok_shape}, cache_shape).compile()
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    logits, cache = prefill_c(params, batch)
    toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    toks.block_until_ready()
    prefill_s = time.perf_counter() - t0
    generated, step_logits = [toks], []
    t0 = time.perf_counter()
    for _ in range(gen):
        logits_t, cache = decode_c(params, {"tokens": toks}, cache)
        toks = jnp.argmax(logits_t, axis=-1).astype(jnp.int32)[:, None]
        generated.append(toks)
        step_logits.append(logits_t)
    jax.block_until_ready(toks)
    decode_s = time.perf_counter() - t0

    dev = jax.devices()[0]
    device = f"{dev.platform}:{dev.device_kind}"
    print(f"[{device}] compile {compile_s:.2f} s (prefill + decode)")
    print(f"[{device}] served {B} sequences x {gen} tokens: prefill "
          f"{prefill_s:.3f} s, decode {B * gen / decode_s:.1f} tok/s")
    return {
        "prefill_logits": np.asarray(logits),
        "tokens": np.asarray(jnp.concatenate(generated, axis=1)),
        "decode_logits": np.asarray(jnp.stack(step_logits, axis=1)),
        "params": params,
        "batch": batch,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="sim", choices=["sim", "local"])
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--replicas", type=int, default=5)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--pages", type=int, default=1024)
    ap.add_argument("--arch", default="qwen3-8b", choices=ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--gen-tokens", type=int, default=16)
    args = ap.parse_args()
    if args.backend == "sim":
        run_sim(args)
    else:
        enable_compilation_cache()
        run_local(args)


if __name__ == "__main__":
    main()
