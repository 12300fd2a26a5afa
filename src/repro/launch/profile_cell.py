import os
# A host-side projection: 512 virtual CPU devices stand in for the pods, so
# the CPU platform is pinned even on a machine that has a TPU attached.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Dry-run profiler: the per-cell debugging view for the §Perf loop.

Lowers one (arch x shape x mesh) cell exactly like dryrun.py and prints the
LARGEST collective contributors (with loop multipliers applied), the
roofline terms, and memory.  This is the 'profile' on a CPU-only container:
the optimized HLO is the ground truth for what the SPMD partitioner will
move over the wire.

Usage:
  PYTHONPATH=src python -m repro.launch.profile_cell --arch qwen2-72b \
      --shape train_4k [--multi-pod] [--remat dots] [--microbatches 4]
"""

import argparse

from .dryrun import lower_cell
from .hlo_analysis import top_collectives


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--remat", default="nothing")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--layout", default="tp",
                    choices=["tp", "fsdp", "serve"])
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()

    import json
    # lower_cell recompiles; reuse its record and re-lower for the text
    rec = lower_cell(
        args.arch, args.shape, multi_pod=args.multi_pod,
        remat_policy=args.remat, microbatches=args.microbatches,
        keep_hlo=True, layout=args.layout,
    )
    print(json.dumps(
        {k: rec[k] for k in (
            "arch", "shape", "mesh", "chips", "compile_seconds",
            "t_compute_s", "t_memory_s", "t_collective_s", "dominant",
            "useful_flops_fraction", "model_flops_util",
        )}, indent=1))
    print("memory/dev: "
          f"{rec['memory']['total_hbm_bytes'] / 1e9:.2f} GB "
          f"(peak {rec['memory']['peak_memory_in_bytes'] / 1e9:.2f} GB, "
          f"temp {rec['memory']['temp_size_in_bytes'] / 1e9:.2f} GB)")
    print("collectives/dev: "
          + ", ".join(f"{k}={v / 1e9:.2f}GB"
                      for k, v in rec["collectives"].items()
                      if k not in ("count",) and v))

    hlo = rec["_hlo_text"]
    print(f"\ntop {args.top} collective contributors "
          "(bytes x loop multipliers, per device):")
    pod = 256 if args.multi_pod else 10 ** 9
    for name, kind, wire, mult in top_collectives(hlo, n=args.top,
                                                  pod_size=pod):
        print(f"  {wire / 1e9:>9.3f} GB  x{mult:<6.0f} {kind:<18} {name}")


if __name__ == "__main__":
    main()
