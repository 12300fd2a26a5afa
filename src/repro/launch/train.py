"""Production training driver.

On a real TPU slice this is the per-host entry point: it builds the
production mesh, shards params/optimizer with the rule table, wires the
IRM-packed streaming pipeline, and runs the fault-tolerant controller
(async checkpoints, restart-on-failure).  On one host it runs the same code
path on the local mesh (every local device on the ``data`` axis), at full
width on TPU chips or with a reduced config (``--smoke``) on a CPU.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
      --steps 20
  # four chips, parameters and optimizer state sharded over ``data``:
  python -m repro.launch.train --arch olmo-1b \
      --seq-len 512 --batch-size 8 --steps 5 --ckpt-every 0
  # on a pod:
  python -m repro.launch.train --arch qwen2-72b --shape train_4k \
      --mesh single-pod
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp

from ..configs import ARCH_NAMES, SHAPES_BY_NAME, get_config
from ..data import StreamingPipeline, synthetic_documents
from ..distributed.context import activation_sharding
from ..distributed.sharding import (batch_shardings, bytes_by_device,
                                    make_rules, param_shardings)
from ..models import build_model, init_params
from ..training import OptimizerConfig, init_opt_state, make_train_step
from ..training.controller import TrainController, TrainControllerConfig
from .compile_cache import enable_compilation_cache
from .mesh import make_local_mesh, make_production_mesh


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_NAMES)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="local",
                    choices=["local", "single-pod", "multi-pod"])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="nothing",
                    choices=["nothing", "dots", "everything"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=100,
                    help="steps between checkpoints; 0 writes none")
    return ap.parse_args(argv)


def run_geometry(args: argparse.Namespace):
    """(config, sequence length, global batch) for these arguments."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    shape = SHAPES_BY_NAME[args.shape]
    seq_len = args.seq_len or (256 if args.smoke else shape.seq_len)
    batch = args.batch_size or (4 if args.smoke else shape.global_batch)
    return cfg, seq_len, batch


def host_batches(cfg, seq_len: int, batch: int) -> Iterator[Dict[str, Any]]:
    """First-Fit-packed batches of synthetic documents, as host arrays."""
    pipe = StreamingPipeline(
        synthetic_documents(cfg.vocab_size, mean_len=seq_len // 3,
                            max_len=4 * seq_len, seed=0),
        seq_len=seq_len, batch_size=batch, prefetch=4,
    )
    for pb in pipe:
        yield {
            "tokens": pb.tokens,
            "labels": pb.labels,
            "segment_ids": pb.segment_ids,
            "positions": pb.positions,
        }


def train(args: argparse.Namespace) -> Dict[str, Any]:
    """Run ``args.steps`` training steps; returns the per-step losses and
    gradient norms, and the parameter bytes each device holds after
    sharding."""
    cfg, seq_len, batch = run_geometry(args)
    mesh = (
        make_local_mesh()
        if args.mesh == "local"
        else make_production_mesh(multi_pod=args.mesh == "multi-pod")
    )
    rules = make_rules(mesh)
    model = build_model(cfg)
    specs = model.param_specs()
    p_shard = param_shardings(specs, mesh, rules)

    print(f"arch={cfg.name} mesh={dict(mesh.shape)} seq={seq_len} "
          f"batch={batch}")
    with mesh, activation_sharding(mesh, rules):
        params = jax.jit(
            lambda k: init_params(specs, k), out_shardings=p_shard
        )(jax.random.PRNGKey(0))
        held = bytes_by_device(params)
        opt_state = init_opt_state(params)
        step_fn = jax.jit(
            make_train_step(
                model,
                OptimizerConfig(decay_steps=max(args.steps, 100)),
                remat_policy=args.remat,
                microbatches=args.microbatches,
            ),
            donate_argnums=(0, 1),
        )
        b_shard = None

        def batches():
            nonlocal b_shard
            for host in host_batches(cfg, seq_len, batch):
                if b_shard is None:
                    b_shard = batch_shardings(
                        {k: jax.ShapeDtypeStruct(v.shape, jnp.int32)
                         for k, v in host.items()},
                        mesh, rules,
                    )
                yield {
                    k: jax.device_put(v, b_shard[k]) for k, v in host.items()
                }

        ctl = TrainController(step_fn, TrainControllerConfig(
            checkpoint_dir=args.ckpt_dir, checkpoint_every=args.ckpt_every,
        ))
        params, opt_state, start = ctl.init_state(
            lambda: (params, opt_state),
        )

        t0 = time.perf_counter()
        losses: List[float] = []
        grad_norms: List[float] = []

        def on_metrics(step, metrics):
            losses.append(float(metrics["loss"]))
            grad_norms.append(float(metrics["grad_norm"]))
            if step % 10 == 0 or step == start + 1:
                print(f"step {step:>5}  loss {losses[-1]:.4f}  "
                      f"grad_norm {grad_norms[-1]:.3f}")

        params, opt_state, summary = ctl.run(
            params, opt_state, batches(), num_steps=args.steps,
            start_step=start, on_metrics=on_metrics,
        )
        dt = time.perf_counter() - t0
        done = summary["final_step"] - start
        print(f"\n{done} steps in {dt:.1f}s "
              f"({done * batch * seq_len / dt:,.0f} tok/s, compile included); "
              f"restarts={summary['restarts']}")
    return {"losses": losses, "grad_norms": grad_norms, "start_step": start,
            "param_bytes_by_device": held, "restarts": summary["restarts"]}


def main() -> None:
    args = parse_args()
    enable_compilation_cache()
    train(args)


if __name__ == "__main__":
    main()
