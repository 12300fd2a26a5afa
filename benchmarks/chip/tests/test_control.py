"""The control of each cell, at a size a test run can hold, on the CPU:
the program's readings stay within the cell's limit and the control's,
the reference one precision lower, all exceed it.  On the chip the same
readings are taken at the cell's own size by ``control.py``."""

from __future__ import annotations

import pytest

import harness
from conftest import tiny_lm_cell, tiny_stream_cell

SEEDS = [2**31 + 1, 2**31 + 2, 2**31 + 3]


@pytest.fixture(scope="module")
def control():
    return harness.load_module(harness.HERE / "control.py")


def _readings(rows):
    (name,) = [k for k in rows[0] if k != "control"]
    return [r[name] for r in rows], [r["control"] for r in rows]


def test_lm_control_is_not_correct(control):
    cell = tiny_lm_cell(check_sequences=12)
    prog, ctrl = _readings([r for _, r in control.lm_readings(cell, SEEDS)])
    limit = cell.limits["served_token_gap"]
    print("program", prog, "control", ctrl, "limit", limit)
    assert max(prog) <= limit < min(ctrl)


def test_stream_control_is_not_correct(control):
    cell = tiny_stream_cell()
    prog, ctrl = _readings([r for _, r in
                            control.stream_readings(cell, SEEDS)])
    limit = cell.limits["gmm_max_err"]
    print("program", prog, "control", ctrl, "limit", limit)
    assert max(prog) <= limit < min(ctrl)
