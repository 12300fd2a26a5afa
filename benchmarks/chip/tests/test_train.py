"""The training cell's check, driven on the CPU at a size a test run can
hold: the sound run comes out correct; each fault planted in the timed
path makes it come out not correct; the control, the reference one
precision lower in the program's place, is not correct on any seed.  The
faults are those a packed training step can have: attention across
documents (the segment mask ignored), positions that do not advance, a
loss on the token after a document's end, half the rows' targets
dropped, an update that leaves the weights as they were, Adam's bias
correction left out.  Positions that run on along the row instead of restarting at
each document are no fault of the step: RoPE sees only the difference of
two positions of one document, which they keep."""

from __future__ import annotations

import numpy as np
import pytest

import harness
from conftest import _limits

SEEDS = [2**31 + 1, 2**31 + 2, 2**33 + 3]


def tiny_train_cell() -> harness.Cell:
    """OLMo-1B's equations and optimizer at d_model 128 over 2 layers, a
    500-id vocabulary padded to 512 rows, 8 rows of 64 a step."""
    cfg = harness.load_json(harness.HERE / "configs" / "olmo-1b-train.json")
    cfg["model"].update(n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
                        head_dim=32, d_ff=256, vocab_size=500)
    cfg.update(global_batch=8, reference_rows_per_block=4)
    traffic = harness.load_json(harness.HERE / "traffic" / "train4.json")
    traffic.update(seq_len=64, doc_mean_len=21, doc_max_len=256,
                   doc_vocab=480)
    return harness.Cell("olmo-1b.train4", 1, cfg, traffic,
                        _limits("olmo-1b.train4"))


def _correct(out) -> bool:
    return all(c.ok for c in out.checks)


def test_train_sound_run_is_correct(drive):
    out = drive(tiny_train_cell(), seconds=2.0)
    assert _correct(out), out.checks
    assert out.attempted >= 2 and out.failed == 0
    assert out.e2e["tokens_per_s"] > 0 and out.e2e["setup_s"] > 0


def _segments_ignored(monkeypatch):
    from repro.models import layers

    def mask(q_idx, kv_idx, seg_q, seg_kv, causal, window):
        m = (seg_q[:, :, None] >= 0) & (seg_kv[:, None, :] != 0)
        return m & (q_idx[None, :, None] >= kv_idx[None, None, :])

    monkeypatch.setattr(layers, "_mask_chunk", mask)


def _emit_then(monkeypatch, change):
    """The packer's batches, altered by ``change(batch)`` as emitted."""
    from repro.data.packing import SequencePacker

    sound = SequencePacker._emit

    def emit(self, rows):
        b = sound(self, rows)
        change(b)
        return b

    monkeypatch.setattr(SequencePacker, "_emit", emit)


def _row_positions(b):
    """Positions counted along the row, not restarted at each document."""
    S = b.positions.shape[1]
    b.positions[:] = np.where(b.segment_ids > 0, np.arange(S), 0)


def _positions_not_advanced(monkeypatch):
    _emit_then(monkeypatch, lambda b: b.positions.fill(0))


def _loss_past_document_end(monkeypatch):
    def change(b):
        seg = b.segment_ids
        last = (seg[:, :-1] > 0) & (seg[:, 1:] > seg[:, :-1])
        b.labels[:, :-1] = np.where(last, b.tokens[:, 1:], b.labels[:, :-1])

    _emit_then(monkeypatch, change)


def _half_rows_dropped(monkeypatch):
    def change(b):
        b.labels[b.labels.shape[0] // 2:] = -1

    _emit_then(monkeypatch, change)


def _optimizer_then(monkeypatch, change):
    """``adamw_update`` with its new weights altered by
    ``change(params, new_params, new_state, cfg)``."""
    from repro.training import train_step

    sound = train_step.adamw_update

    def update(params, grads, state, cfg):
        new, st, met = sound(params, grads, state, cfg)
        return change(params, new, st, cfg), st, met

    monkeypatch.setattr(train_step, "adamw_update", update)


def _update_not_applied(monkeypatch):
    _optimizer_then(monkeypatch, lambda p, new, st, cfg: p)


def _no_bias_correction(monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.training.optimizer import lr_at

    def change(p, new, st, cfg):
        lr = lr_at(cfg, st["step"])
        return jax.tree.map(
            lambda w, m, v: w - lr * (m / (jnp.sqrt(v) + cfg.eps)
                                      + cfg.weight_decay * w),
            p, st["m"], st["v"])

    _optimizer_then(monkeypatch, change)


@pytest.mark.parametrize("fault", [_segments_ignored,
                                   _positions_not_advanced,
                                   _loss_past_document_end,
                                   _half_rows_dropped,
                                   _update_not_applied,
                                   _no_bias_correction])
def test_train_fault_is_not_correct(drive, monkeypatch, fault):
    fault(monkeypatch)
    out = drive(tiny_train_cell(), seconds=1.0)
    assert not _correct(out), out.checks


def test_row_positions_change_nothing(drive, monkeypatch):
    """Positions not restarted at a document's start shift every position
    of a document alike, and RoPE's scores depend only on the difference
    of two positions of one document: the step is the same step, and the
    check rightly passes it (to float32's rounding of larger angles)."""
    sound = drive(tiny_train_cell(), seconds=0.5)
    _emit_then(monkeypatch, _row_positions)
    shifted = drive(tiny_train_cell(), seconds=0.5)
    assert _correct(shifted), shifted.checks
    (a, b) = [o.checks[0].value for o in (sound, shifted)]
    assert abs(a - b) < 1e-4


def test_train_control_is_not_correct():
    control = harness.load_module(harness.HERE / "control_train.py")
    cell = tiny_train_cell()
    prog, ctrl = [], []
    for _, r in control.readings(cell, SEEDS):
        checks = {k: [harness.Check(k, v, cell.limits[k]) for v in r[k]]
                  for k in control.NUMBERS}
        prog.append(all(c[0].ok for c in checks.values()))
        ctrl.append(all(c[1].ok for c in checks.values()))
        print(r)
    assert all(prog) and not any(ctrl)
