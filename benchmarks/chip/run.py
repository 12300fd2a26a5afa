"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout on a machine with the chips the cell asks for.
The cell's configuration, traffic, limits, driver and per-layer readers
are found by the names in ``BENCHMARK.json``.  Exits 3, printing no
result, when JAX finds no TPU or too few chips.  Otherwise the last lines
on standard error are the numbers that decide ``correct``, each beside its
limit, and the last line on standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402


def metrics_for(bench, cell_name: str, trace: bool, out) -> dict:
    """The cell's end-to-end metrics (trace 0) or per-layer metrics
    (trace 1) that apply to it, each with its unit; a reader that finds
    nothing leaves its metric out."""
    rows = bench["per_layer"] if trace else bench["end_to_end"]
    found = {}
    for m in rows:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        value = harness.reader(m["name"])(out) if trace else out.e2e.get(
            m["name"])
        if value is not None:
            found[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return found


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = harness.manifest()
    cell = harness.find_cell(args.workload, bench)
    t_main = time.perf_counter()
    cache = harness.enable_compile_cache()  # imports JAX
    cached = harness.cache_entries(cache)
    t_jax = time.perf_counter()
    try:
        devs = harness.tpu_devices(cell.chips)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    t_devs = time.perf_counter()
    trace = bool(args.trace)
    out = harness.driver(cell).run(cell, args.seed, args.seconds, trace, T0,
                                   devs)
    out.notes.insert(0, f"start: harness imported {t_main - T0:.3f} s, JAX "
                        f"imported {t_jax - t_main:.3f} s, devices found "
                        f"{t_devs - t_jax:.3f} s")

    correct = all(c.ok for c in out.checks)
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed,
              "metrics": metrics_for(bench, cell.name, trace, out),
              "device": device}
    s = out.layer.get("trace")
    if trace and s is not None:
        device["busy_s"] = s.busy_s
        device["window_s"] = s.window_s
        result["breakdown"] = {"device_ops": s.top_ops(10),
                               "idle_gaps": s.idle_gaps[:10]}
    result["checks"] = {c.name: {"value": c.value if math.isfinite(c.value)
                                 else str(c.value), "limit": c.limit}
                        for c in out.checks}

    if trace and s is not None:
        out.notes += [f"program {k}: {len(v)} runs, {sum(v):.6f} s"
                      for k, v in sorted(s.modules.items())]
        out.notes += [f"host span {k}: {n} times, {t:.6f} s"
                      for k, (t, n) in sorted(s.spans.items())]
    out.notes.append(f"compile cache: {cached} entries before the run, "
                     f"{harness.cache_entries(cache)} after (a run that adds "
                     f"entries compiled in its set-up)")
    for line in out.notes:
        print(f"[{dev.device_kind} x{len(devs)}] {line}", file=sys.stderr)
    for c in out.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
