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
from typing import Any, Dict, Iterable, Iterator, List, Optional

import jax
import jax.numpy as jnp

from ..configs import ARCH_NAMES, SHAPES_BY_NAME, get_config
from ..data import StreamingPipeline, synthetic_documents
from ..distributed.context import activation_sharding
from ..distributed.sharding import (batch_shardings, bytes_by_device,
                                    make_rules, param_shardings)
from ..models import build_model, init_params
from ..obs.spans import span
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
    ap.add_argument("--seed", type=int, default=0,
                    help="draws the initial parameters and the documents")
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


def host_batches(cfg, seq_len: int, batch: int, *, seed: int = 0,
                 mean_len: Optional[float] = None, sigma: float = 0.9,
                 max_len: Optional[int] = None,
                 vocab: Optional[int] = None) -> Iterator[Dict[str, Any]]:
    """First-Fit-packed batches of synthetic documents, as host arrays.

    Document lengths are lognormal with mean ``mean_len`` (default a third
    of a row) and shape ``sigma``, clipped to [8, ``max_len``] (default four
    rows); token ids are Zipf over the first ``vocab`` ids (default the
    model's vocabulary); ``seed`` draws both."""
    docs = synthetic_documents(
        vocab or cfg.vocab_size, mean_len=mean_len or seq_len // 3,
        sigma=sigma, max_len=max_len or 4 * seq_len, seed=seed)
    pipe = StreamingPipeline(docs, seq_len=seq_len, batch_size=batch,
                             prefetch=4)
    for pb in pipe:
        yield {
            "tokens": pb.tokens,
            "labels": pb.labels,
            "segment_ids": pb.segment_ids,
            "positions": pb.positions,
        }


def device_batches(hosts: Iterable[Dict[str, Any]], mesh,
                   rules) -> Iterator[Dict[str, Any]]:
    """Host batches placed on the mesh by ``batch_shardings``; each wait
    for the next one (packing, transfer) is a ``repro.train.batch`` span."""
    hosts = iter(hosts)
    shard = None
    while True:
        with span("repro.train.batch"):
            host = next(hosts, None)
            if host is None:
                return
            if shard is None:
                shard = batch_shardings(
                    {k: jax.ShapeDtypeStruct(v.shape, jnp.int32)
                     for k, v in host.items()}, mesh, rules)
            dev = {k: jax.device_put(v, shard[k]) for k, v in host.items()}
        yield dev


def init_sharded_params(model, mesh, rules, seed: int = 0):
    """The model's initial parameters from ``PRNGKey(seed)``, made on the
    mesh in their ``param_shardings``; the same seed gives the same
    parameters."""
    specs = model.param_specs()
    return jax.jit(lambda k: init_params(specs, k),
                   out_shardings=param_shardings(specs, mesh, rules)
                   )(jax.random.PRNGKey(seed))


def jit_train_step(model, opt_cfg: OptimizerConfig, *, remat: str = "nothing",
                   microbatches: int = 1):
    """``make_train_step`` jitted with the parameters and the optimizer
    state donated: bf16 compute copy, float32 master weights and Adam
    moments.  Trace it under the mesh's ``activation_sharding``."""
    return jax.jit(
        make_train_step(model, opt_cfg, remat_policy=remat,
                        microbatches=microbatches),
        donate_argnums=(0, 1),
    )


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

    print(f"arch={cfg.name} mesh={dict(mesh.shape)} seq={seq_len} "
          f"batch={batch}")
    with mesh, activation_sharding(mesh, rules):
        params = init_sharded_params(model, mesh, rules, args.seed)
        held = bytes_by_device(params)
        opt_state = init_opt_state(params)
        step_fn = jit_train_step(
            model, OptimizerConfig(decay_steps=max(args.steps, 100)),
            remat=args.remat, microbatches=args.microbatches)
        batches = device_batches(host_batches(cfg, seq_len, batch,
                                              seed=args.seed), mesh, rules)

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
            params, opt_state, batches, num_steps=args.steps,
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
