"""Shared set-up of the benchmark's own tests (run by explicit path, on
the CPU: ``JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest
benchmarks/chip/tests``).  Cells here are the real ones cut to a size a
test run can hold; the chip runs them at full size."""

from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))

import harness  # noqa: E402


def _limits(cell: str):
    raw = harness.load_json(harness.HERE / "limits" / f"{cell}.json")
    return {k: float(v["limit"]) for k, v in raw["checks"].items()}


def tiny_lm_cell(check_sequences: int = 64) -> harness.Cell:
    """OLMo-1B's equations at d_model 256 over 2 layers; the embedding
    std is scaled so that logits have OLMo-1B's spread."""
    cfg = harness.load_json(harness.HERE / "configs" / "olmo-1b.json")
    cfg["model"].update(n_layers=2, d_model=256, n_heads=4, n_kv_heads=4,
                        head_dim=64, d_ff=512, vocab_size=1024)
    cfg["prompt_vocab"] = 1000
    cfg["embed_std"] = 0.02 * (2048 / 256) ** 0.5
    traffic = dict(kind="lm_static", batch=4, prompt_len=16, gen_tokens=8,
                   check_sequences=check_sequences)
    return harness.Cell("olmo-1b.decode", 1, cfg, traffic,
                        _limits("olmo-1b.decode"))


def tiny_stream_cell() -> harness.Cell:
    """The paper's cluster and IRM, 24 images a batch, a (2, 128, 256)
    grouped matmul in interpret mode."""
    cfg = harness.load_json(harness.HERE / "configs" / "microscopy-gmm.json")
    cfg["payload"].update(experts=2, rows=128, dim=256, interpret=True)
    cfg["cluster"]["t_max"] = 150.0
    traffic = harness.load_json(harness.HERE / "traffic" / "batch.json")
    traffic.update(n_images=24, sample_range=20, sample_calls=4)
    return harness.Cell("microscopy-gmm.batch", 1, cfg, traffic,
                        _limits("microscopy-gmm.batch"))


@pytest.fixture
def drive(monkeypatch):
    """Runs a cell on the CPU, past the harness's look for a chip."""
    import jax

    monkeypatch.setattr(harness, "peaks", lambda kind: {
        "peak_flops_bf16": 1e12, "hbm_bytes_per_s": 1e11})

    def go(cell, driver=None, seconds=1.0, trace=False, seed=2**31 + 77):
        mod = driver or harness.driver(cell)
        return mod.run(cell, seed, seconds, trace, time.perf_counter(),
                       jax.devices())

    return go
