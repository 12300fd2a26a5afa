"""Read a cell's correctness number for the program and for its control,
on a dozen seeds or more, in one process on the chip.

    python3 benchmarks/chip/control.py --workload olmo-1b.decode \
        --seeds 11 12 13 ...

The program's readings come from the timed path at the cell's own size:
for a language-model cell, enough cycles of the cell's batch through the
same jitted prefill and decode programs to give as many sequences as a run
checks, sampled and read by the float32 reference exactly as a run does
(``served_token_gap``); for a stream
cell, the payload's kernel on the seed's operands against the float32
grouped matmul (``gmm_max_err``).  The control's readings put the
reference in the program's place, one precision lower: the float8
forward's first choice at each served position, or float8 operands.
Both readings of each seed go through the same ``Check`` against the
cell's limit that a run makes.  Prints one JSON line per seed, with
whether each came out correct, and a summary: the lower reading (largest
of the program's), the upper (smallest of the control's), their ratio,
and whether the program was correct on every seed and the control on
none.
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

import numpy as np  # noqa: E402

import harness  # noqa: E402


def lm_readings(cell, seeds):
    from repro.models import build_model, pad_vocab
    from reference.olmo import gaps_and_control

    lm = harness.load_module(HERE / "drivers" / "lm.py")
    cfg, tr = cell.config, cell.traffic
    m = cfg["model"]
    model = build_model(lm.arch(cfg))
    P, G = tr["prompt_len"], tr["gen_tokens"]
    prefill, step = lm.serving_programs(model, P + G - 1)
    n = int(tr["check_sequences"])
    for seed in seeds:
        weights = lm.make_weights(cfg, seed, pad_vocab(m["vocab_size"]))
        params = lm.program_params(model, weights)
        served = []
        for cycle in range(-(-n // tr["batch"])):  # as many as a run checks
            cyc = lm.Served(lm.generator.lm_prompts(tr, cfg["prompt_vocab"],
                                                    seed, cycle))
            tok, cache = prefill(params, lm.prompt_batch(cyc.prompts))
            cyc.steps.append(np.asarray(tok)[:, 0])
            for _ in range(G - 1):
                tok, cache = step(params, tok, cache)
                cyc.steps.append(np.asarray(tok)[:, 0])
            del tok, cache
            served.append(cyc)
        del params
        prog = ctrl = 0.0
        for p, t in lm.sample(served, G, n, seed):
            a, b = gaps_and_control(weights, {"rope_theta": m["rope_theta"]},
                                    p, t, m["vocab_size"])
            prog, ctrl = max(prog, float(a.max())), max(ctrl, float(b.max()))
        yield seed, {"served_token_gap": prog, "control": ctrl}
        del weights


def stream_readings(cell, seeds):
    from repro.runtime import payloads
    from reference.gmm import grouped_matmul, grouped_matmul_fp8

    st = harness.load_module(HERE / "drivers" / "stream.py")
    pk = dict(cell.config["payload"])
    payload = payloads.make_payload(pk.pop("name"), **pk)
    for seed in seeds:
        x, w, sizes = st.give_operands(payload, pk, seed)
        ref = np.asarray(grouped_matmul(x, w, sizes))
        yield seed, {
            "gmm_max_err": harness.max_scaled_error(
                np.asarray(payload._compute()), ref),
            "control": harness.max_scaled_error(
                np.asarray(grouped_matmul_fp8(x, w, sizes)), ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    harness.enable_compile_cache()
    try:
        devs = harness.tpu_devices(cell.chips)
    except harness.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 3
    read = lm_readings if cell.config["driver"] == "lm" else stream_readings
    rows = []
    for seed, r in read(cell, args.seeds):
        (name,) = [k for k in r if k != "control"]
        # both through the check a run makes, against the cell's limit
        prog = harness.Check(name, r[name], cell.limits[name])
        ctrl = harness.Check(name, r["control"], cell.limits[name])
        rows.append((prog, ctrl))
        print(json.dumps({"seed": seed, **r, "limit": prog.limit,
                          "program_correct": prog.ok,
                          "control_correct": ctrl.ok,
                          "t": time.perf_counter()}), flush=True)
    lower = max(p.value for p, _ in rows)
    upper = min(c.value for _, c in rows)
    print(json.dumps({"workload": cell.name, "device": devs[0].device_kind,
                      "number": name, "limit": rows[0][0].limit,
                      "seeds": len(rows), "lower": lower, "upper": upper,
                      "ratio": upper / max(lower, 1e-30),
                      "program_correct_on_all": all(p.ok for p, _ in rows),
                      "control_correct_on_none": not any(c.ok for _, c in
                                                         rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
