"""The entry point's contract off the chip: no TPU, no result."""

from __future__ import annotations

import os
import subprocess
import sys

import harness


def test_exits_nonzero_and_prints_nothing_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(harness.HERE / "run.py"), "--workload",
         "olmo-1b.decode", "--seed", str(2**31 + 9), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=harness.ROOT, timeout=300)
    assert p.returncode == 3
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_every_cell_finds_its_files():
    bench = harness.manifest()
    for w in bench["workloads"]:
        cell = harness.find_cell(w["name"], bench)
        assert cell.limits and cell.config["driver"] in ("lm", "stream")
        assert (harness.HERE / "drivers" / f"{cell.config['driver']}.py").exists()
    for m in bench["per_layer"]:
        assert callable(harness.reader(m["name"]))
