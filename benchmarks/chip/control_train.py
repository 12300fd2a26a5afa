"""Read a training cell's correctness numbers for the program and for its
control, on several seeds, in one process on the chips.

    python3 benchmarks/chip/control_train.py --workload olmo-1b.train4 \
        --seeds 11 12 13 ... [--seconds 300]

For each seed, as a run of the cell does: the program's first step from
the seed's initial weights on the first packed batch, its gradient read
from Adam's first moment, and the float32 reference's loss and gradient
of the same batch (``drivers/train.py``); the program's readings are the
step's loss, gradient and change of the weights against the reference's
loss, gradient and plain AdamW's change from that gradient.  The
control's readings put the reference one precision lower in the
program's place: its loss, its gradient with float8 (e4m3) matmul
operands and AdamW's change from that gradient, against the float32
ones.  The program's state stays on the chips (no step follows).  Every reading goes through the same ``Check`` against the cell's
limit that a run makes.  Prints one JSON line per seed, and a summary per
number: the largest of the program's readings, the smallest of the
control's, and whether the program was correct on every seed and the
control on none (a control is not correct when any of its numbers fails).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402

NUMBERS = ("loss_rel_err", "grad_rel_err", "update_rel_err")


def readings(cell, seeds):
    """``(seed, {number: (program, control)})`` for each seed."""
    from repro.distributed.context import activation_sharding
    from repro.distributed.sharding import make_rules
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import (device_batches, host_batches,
                                    init_sharded_params, jit_train_step)
    from repro.models import build_model
    from repro.training import init_opt_state

    tr = harness.load_module(HERE / "drivers" / "train.py")
    from reference import olmo_train
    cfg, traffic = cell.config, cell.traffic
    a = tr.arch(cfg)
    model, opt = build_model(a), tr.opt_config(cfg)
    B, S = int(cfg["global_batch"]), int(traffic["seq_len"])
    mesh = make_local_mesh()
    rules = make_rules(mesh)
    with mesh, activation_sharding(mesh, rules):
        step = jit_train_step(model, opt, remat=cfg["remat"])
        for seed in seeds:
            pseed, dseed = tr.seeds(seed)
            first = next(host_batches(a, S, B, seed=dseed,
                                      **tr.doc_params(traffic)))
            params = init_sharded_params(model, mesh, rules, pseed)
            opt_state = init_opt_state(params)
            params, opt_state, met = step(
                params, opt_state, next(device_batches([first], mesh, rules)))
            loss1, gnorm1 = float(met["loss"]), float(met["grad_norm"])
            after = tr.ref_layout(params)
            grads = tr.first_moment_grads(tr.ref_layout(opt_state["m"]),
                                          gnorm1, opt)
            del params, opt_state, met
            ref_loss, _, ref_g, w0 = tr.reference(model, mesh, rules, pseed,
                                                  first, cfg)
            prog = (tr.loss_error(loss1, ref_loss),
                    max(tr.tensor_errors(grads, ref_g).values()),
                    max(tr.update_errors(after, w0, ref_g, cfg).values()))
            del after, grads
            low_loss, _, low_g, _ = tr.reference(model, mesh, rules, pseed,
                                                 first, cfg, fp8=True, w=w0)
            low = (tr.loss_error(low_loss, ref_loss),
                   max(tr.tensor_errors(low_g, ref_g).values()),
                   max(tr.update_errors(
                       {k: w0[k] + d for k, d in olmo_train.adamw_first_update(
                           w0, low_g, cfg["optimizer"]).items()},
                       w0, ref_g, cfg).values()))
            del w0, ref_g, low_g
            yield seed, dict(zip(NUMBERS, zip(prog, low)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=float("inf"),
                    help="start no further seed after this many seconds")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    cell = harness.find_cell(args.workload)
    harness.enable_compile_cache()
    try:
        devs = harness.tpu_devices(cell.chips)
    except harness.NoChip as e:
        print(f"control_train.py: {e}", file=sys.stderr)
        return 3
    rows = []
    for seed, r in readings(cell, args.seeds):
        checks = {k: [harness.Check(k, v, cell.limits[k]) for v in r[k]]
                  for k in NUMBERS}
        rows.append(checks)
        print(json.dumps({
            "seed": seed, **{k: list(r[k]) for k in NUMBERS},
            "program_correct": all(c[0].ok for c in checks.values()),
            "control_correct": all(c[1].ok for c in checks.values()),
            "t": time.perf_counter() - t0}), flush=True)
        if time.perf_counter() - t0 > args.seconds:
            break
    print(json.dumps({
        "workload": cell.name, "device": devs[0].device_kind,
        "seeds": len(rows),
        "numbers": {k: {"limit": cell.limits[k],
                        "lower": max(r[k][0].value for r in rows),
                        "upper": min(r[k][1].value for r in rows)}
                    for k in NUMBERS},
        "program_correct_on_all": all(c[0].ok for r in rows
                                      for c in r.values()),
        "control_correct_on_none": not any(
            all(c[1].ok for c in r.values()) for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
