import os
# A host-side projection: 512 virtual CPU devices stand in for the pods, so
# the CPU platform is pinned even on a machine that has a TPU attached.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: parameters and
inputs are ``ShapeDtypeStruct`` stand-ins (zero allocation), the jit'd step
is lowered with the production shardings and compiled by XLA's SPMD
partitioner for the 16x16 (single-pod) and 2x16x16 (multi-pod) meshes.
``memory_analysis()`` proves the per-device footprint fits; the cost /
collective numbers feed EXPERIMENTS.md §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both \
      --out results/dryrun.json
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..configs import ARCH_NAMES, SHAPES_BY_NAME, cells_for, get_config
from ..distributed.context import activation_sharding
from ..distributed.sharding import (
    batch_shardings,
    cache_shardings,
    make_rules,
    param_shardings,
)
from ..models import abstract_params, build_model, cache_specs, input_specs
from ..models.params import Spec, tree_bytes
from ..training import OptimizerConfig, make_train_step
from .analysis import cost_summary, memory_summary, peaks
from .hlo_analysis import analyze_hlo_text
from .mesh import make_production_mesh

PER_POD_CHIPS = 256
# the chip the projected 16x16 pods are made of
HW = peaks("TPU v5 lite")


def _abstract_opt_state(param_specs_tree: Any) -> Any:
    def sds(s: Spec) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(s.shape, jnp.float32)

    is_spec = lambda x: isinstance(x, Spec)  # noqa: E731
    return {
        "m": jax.tree.map(sds, param_specs_tree, is_leaf=is_spec),
        "v": jax.tree.map(sds, param_specs_tree, is_leaf=is_spec),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def lower_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    remat_policy: str = "nothing",
    microbatches: int = 1,
    param_dtype=jnp.float32,
    keep_hlo: bool = False,
    layout: str = "tp",
) -> Dict[str, Any]:
    """Lower + compile one cell; returns the roofline record."""
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(mesh, layout)
    n_chips = mesh.devices.size

    specs = model.param_specs()
    p_shard = param_shardings(specs, mesh, rules)
    batch = input_specs(cfg, shape)
    b_shard = batch_shardings(batch, mesh, rules, decode=(shape.kind == "decode"))

    t0 = time.time()
    if shape.kind == "train":
        params = abstract_params(specs)  # fp32 master
        opt_state = _abstract_opt_state(specs)
        o_shard = {
            "m": p_shard,
            "v": p_shard,
            "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        }
        step_fn = make_train_step(
            model,
            OptimizerConfig(),
            remat_policy=remat_policy,
            microbatches=microbatches,
            grad_shardings=p_shard,
        )
        jitted = jax.jit(
            step_fn,
            in_shardings=(p_shard, o_shard, b_shard),
            donate_argnums=(0, 1),
        )
        with mesh, activation_sharding(mesh, rules):
            lowered = jitted.lower(params, opt_state, batch)
    elif shape.kind == "prefill":
        params = abstract_params(specs, dtype=jnp.bfloat16)
        jitted = jax.jit(
            lambda p, b: model.prefill(p, b, max_len=shape.seq_len),
            in_shardings=(p_shard, b_shard),
        )
        with mesh, activation_sharding(mesh, rules):
            lowered = jitted.lower(params, batch)
    else:  # decode
        params = abstract_params(specs, dtype=jnp.bfloat16)
        cache = cache_specs(cfg, shape)
        c_shard = cache_shardings(cache, mesh, rules)
        jitted = jax.jit(
            lambda p, b, c: model.decode_step(p, b, c),
            in_shardings=(p_shard, b_shard, c_shard),
            donate_argnums=(2,),
        )
        with mesh, activation_sharding(mesh, rules):
            lowered = jitted.lower(params, batch, cache)

    compiled = lowered.compile()
    compile_s = time.time() - t0

    mem = memory_summary(compiled)
    cost = cost_summary(compiled)  # XLA's own (loop bodies counted once)
    hlo = analyze_hlo_text(
        compiled.as_text(), pod_size=PER_POD_CHIPS if multi_pod else 10**9
    )

    total_params, active_params = cfg.param_counts()
    record: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(n_chips),
        "compile_seconds": round(compile_s, 1),
        "param_count": total_params,
        "active_param_count": active_params,
        "param_bytes_global": tree_bytes(abstract_params(specs, dtype=param_dtype)),
        "memory": mem,
        "xla_cost": cost,
        "flops_per_dev": hlo.flops,
        "dot_bytes_per_dev": hlo.dot_bytes,
        "collectives": dict(hlo.coll, total=hlo.coll_bytes,
                            ici=hlo.ici_bytes, dcn=hlo.dcn_bytes,
                            count=hlo.coll_count),
        "remat_policy": remat_policy,
        "microbatches": microbatches,
        "layout": layout,
    }
    record.update(roofline_terms(record, shape))
    if keep_hlo:
        record["_hlo_text"] = compiled.as_text()
    return record


def roofline_terms(record: Dict[str, Any], shape) -> Dict[str, Any]:
    """Three roofline terms (seconds per step, per chip).

    FLOPs/bytes come from the trip-count-aware HLO analysis (XLA's
    cost_analysis counts loop bodies once — see hlo_analysis.py).  The
    memory term uses dot operand/result traffic as the HBM proxy (weights,
    activations, KV reads are all dot operands; elementwise traffic is
    fusion-resident).  The collective term takes the slower of the ICI and
    DCN paths.
    """
    flops = record["flops_per_dev"]
    bytes_acc = max(
        record["dot_bytes_per_dev"], record["xla_cost"]["bytes_accessed"]
    )
    t_compute = flops / HW["peak_flops_bf16"]
    t_memory = bytes_acc / HW["hbm_bw"]
    t_collective = (
        record["collectives"]["ici"] / HW["ici_bw"]
        + record["collectives"]["dcn"] / HW["dcn_bw"]
    )
    dominant = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_collective),
        key=lambda kv: kv[1],
    )[0]
    # MODEL_FLOPS: 6*N*D for training, 2*N*D for inference (per step, global)
    n_active = record["active_param_count"]
    tokens = (
        shape.global_batch * shape.seq_len
        if shape.kind in ("train", "prefill")
        else shape.global_batch
    )
    # enc-dec (seamless): S is split S/2 encoder + S/2 decoder and each
    # half only passes through its own stack — 6*N_total*(S/2) overall
    if get_config(record["arch"]).encdec and shape.kind in ("train", "prefill"):
        tokens //= 2
    mult = 6 if shape.kind == "train" else 2
    model_flops_global = mult * n_active * tokens
    model_flops_per_chip = model_flops_global / record["chips"]
    useful = model_flops_per_chip / flops if flops else 0.0
    bound = max(t_compute, t_memory, t_collective)
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "dominant": dominant,
        "model_flops_global": model_flops_global,
        "useful_flops_fraction": useful,
        "roofline_step_s": bound,
        "model_flops_util": (
            model_flops_per_chip / HW["peak_flops_bf16"] / bound if bound else 0.0
        ),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_NAMES + [None])
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--shapes", default=None,
                    help="comma-separated shape filter for --all")
    ap.add_argument("--multi-pod", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--remat", default="nothing")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--layout", default="tp",
                    choices=["tp", "fsdp", "serve"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        keep = set(args.shapes.split(",")) if args.shapes else None
        for arch in ARCH_NAMES:
            cfg = get_config(arch)
            for shape in cells_for(cfg):
                if keep and shape.name not in keep:
                    continue
                cells.append((arch, shape.name))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[
        args.multi_pod
    ]

    results = []
    for arch, shape_name in cells:
        for mp in meshes:
            tag = f"{arch} x {shape_name} x {'2x16x16' if mp else '16x16'}"
            try:
                rec = lower_cell(
                    arch, shape_name, multi_pod=mp,
                    remat_policy=args.remat, microbatches=args.microbatches,
                    layout=args.layout,
                )
                results.append(rec)
                print(
                    f"[OK] {tag}: compile={rec['compile_seconds']}s "
                    f"hbm/dev={rec['memory']['total_hbm_bytes']/1e9:.2f}GB "
                    f"flops/dev={rec['flops_per_dev']:.3e} "
                    f"coll/dev={rec['collectives']['total']/1e6:.1f}MB "
                    f"dominant={rec['dominant']} "
                    f"useful={rec['useful_flops_fraction']:.2f} "
                    f"mfu_bound={rec['model_flops_util']:.3f}",
                    flush=True,
                )
            except Exception as e:  # a failure here is a bug in the system
                results.append(
                    {"arch": arch, "shape": shape_name,
                     "mesh": "2x16x16" if mp else "16x16",
                     "error": f"{type(e).__name__}: {e}"}
                )
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {len(results)} records to {args.out}")

    n_fail = sum(1 for r in results if "error" in r)
    print(f"\n{len(results) - n_fail}/{len(results)} cells OK")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
