"""Smoke coverage for the ``python -m repro.scenarios.run`` entry point.

One fast subprocess run pins the actual module invocation (import graph,
argparse wiring, exit codes); the in-process cases cover the CLI surface —
listing, sweeps, error paths — without paying process startup per case.
"""

import os
import subprocess
import sys

import pytest

from repro.scenarios.run import main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(*argv):
    return main(list(argv))


def test_module_entry_point_smoke():
    """The real ``python -m`` invocation: single scenario, tiny horizon,
    serial (--jobs 1)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    proc = subprocess.run(
        [sys.executable, "-m", "repro.scenarios.run", "synthetic",
         "--smoke", "--jobs", "1", "--t-max", "240"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    assert "scenario 'synthetic'" in proc.stdout
    assert "makespan_s" in proc.stdout


def test_cli_list_shows_catalogue(capsys):
    assert run_cli("--list") == 0
    out = capsys.readouterr().out
    for name in ("synthetic", "microscopy", "microscopy-mem", "mixed-accel"):
        assert name in out


def test_cli_list_shows_dims_and_policy_family(capsys):
    """--list prints each scenario's resource dims and its policy family."""
    assert run_cli("--list") == 0
    out = capsys.readouterr().out
    header, *rows = out.splitlines()
    assert "dims" in header and "policies" in header
    by_name = {r.split()[0]: r for r in rows if r and not r.startswith("-")}
    # scalar scenario: cpu-only dims, Any-Fit family
    assert "cpu " in by_name["synthetic"] or "cpu\t" in by_name["synthetic"]
    assert "any-fit" in by_name["synthetic"]
    # vector scenarios: their extra dimension and the vector family
    assert "cpu+mem" in by_name["microscopy-mem"]
    assert "vector" in by_name["microscopy-mem"]
    assert "cpu+accel" in by_name["mixed-accel"]


@pytest.mark.timeout(120)
def test_cli_live_backend_smoke(capsys):
    """--backend live drives the asyncio runtime through the same CLI."""
    assert run_cli("microscopy", "--smoke", "--backend", "live",
                   "--time-scale", "0.005", "--jobs", "1") == 0
    out = capsys.readouterr().out
    assert "backend 'live'" in out
    assert "makespan_s" in out


def test_cli_multiproc_refuses_device_payload(capsys):
    """``--backend multiproc --payload jax`` fails fast and says why."""
    assert run_cli("microscopy", "--smoke", "--backend", "multiproc",
                   "--payload", "jax") == 2
    assert "only one process may hold" in capsys.readouterr().err


def test_cli_parallel_sweep_refuses_device_payload(capsys):
    assert run_cli("microscopy", "--smoke", "--backend", "live",
                   "--payload", "jax", "--policy", "first-fit,best-fit",
                   "--jobs", "2") == 2
    assert "accelerator belongs to one process" in capsys.readouterr().err


def test_cli_unknown_scenario_exits_2(capsys):
    assert run_cli("no-such-scenario") == 2
    assert "unknown scenario" in capsys.readouterr().err


def test_cli_unknown_policy_exits_2(capsys):
    assert run_cli("synthetic", "--smoke", "--policy", "no-such-fit") == 2
    assert "unknown packing algorithm" in capsys.readouterr().err


def test_cli_vector_scenario_smoke(capsys):
    assert run_cli("microscopy-mem", "--smoke", "--jobs", "1") == 0
    out = capsys.readouterr().out
    assert "mean_scheduled_mem_active" in out
    assert "bottleneck_dim: mem" in out


def test_cli_writes_artifacts(tmp_path, capsys):
    assert run_cli("synthetic", "--smoke", "--jobs", "1",
                   "--t-max", "240", "--out", str(tmp_path)) == 0
    capsys.readouterr()
    files = {p.name for p in tmp_path.iterdir()}
    assert "synthetic_summary.json" in files
    assert any(f.endswith(".csv") for f in files)
