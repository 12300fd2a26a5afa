"""Each cell's run, driven past the look for a chip with its timed path
broken underneath, must come out not correct; the same run unbroken must
come out correct.  One case per fault the cell can have: a step that
returns its state unchanged, part of the batch left out, a token or an
answer altered where it is produced; for the stream, an image lost and
the broker's order broken."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import harness
from conftest import tiny_lm_cell, tiny_stream_cell


def _correct(out) -> bool:
    return all(c.ok for c in out.checks)


# ---------------------------------------------------------------------------
# language-model cells
# ---------------------------------------------------------------------------


def _lm_driver():
    return harness.load_module(harness.HERE / "drivers" / "lm.py")


def _token_altered(mod):
    sound = mod.serving_programs

    def programs(model, max_len):
        prefill, step = sound(model, max_len)

        def bad_step(params, tok, cache):
            tok, cache = step(params, tok, cache)
            return tok.at[0, 0].set((tok[0, 0] + 1) % 1000), cache

        return prefill, bad_step

    return programs


def _state_unchanged(mod):
    sound = mod.serving_programs

    def programs(model, max_len):
        prefill, _ = sound(model, max_len)

        @jax.jit
        def stale_step(params, tok, cache):
            logits, _ = model.decode_step(params, {"tokens": tok}, cache)
            return jnp.argmax(logits, -1).astype(jnp.int32)[:, None], cache

        return prefill, stale_step

    return programs


def _half_batch(mod):
    sound = mod.serving_programs

    def programs(model, max_len):
        prefill, step = sound(model, max_len)

        def half_prefill(params, batch):
            half = jax.tree.map(lambda a: a[: a.shape[0] // 2], batch)
            tok, cache = prefill(params, half)
            # the left-out rows get the kept rows' tokens and K/V
            twice = lambda a, axis: jnp.concatenate([a, a], axis)  # noqa: E731
            return twice(tok, 0), {
                "blocks": jax.tree.map(lambda a: twice(a, 1), cache["blocks"]),
                "len": twice(cache["len"], 0)}

        return half_prefill, step

    return programs


def test_lm_sound_run_is_correct(drive):
    out = drive(tiny_lm_cell())
    assert _correct(out), out.checks
    assert out.e2e["tokens_per_s"] > 0 and out.e2e["setup_s"] > 0


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch])
def test_lm_fault_is_not_correct(drive, monkeypatch, fault):
    mod = _lm_driver()
    monkeypatch.setattr(mod, "serving_programs", fault(mod))
    out = drive(tiny_lm_cell(), driver=mod)
    assert not _correct(out), out.checks


# ---------------------------------------------------------------------------
# stream cells
# ---------------------------------------------------------------------------


def test_stream_sound_run_is_correct(drive):
    out = drive(tiny_stream_cell())
    assert _correct(out), out.checks
    assert out.e2e["images_per_s"] > 0 and out.failed == 0


def _break_compute(monkeypatch, broken):
    """The payload built sound (its own warm-up check passes), then its
    per-message compute replaced by ``broken(sound_compute)``."""
    from repro.runtime import payloads

    sound = payloads.make_payload

    def make(name, **kw):
        p = sound(name, **kw)
        inner = p._compute
        p._compute = lambda: broken(inner)
        return p

    monkeypatch.setattr(payloads, "make_payload", make)


def _answer_altered(monkeypatch):
    _break_compute(monkeypatch, lambda compute: compute() * 1.05)


def _answer_not_computed(monkeypatch):
    _break_compute(monkeypatch, lambda compute: jnp.zeros_like(compute()))


def _image_lost(monkeypatch):
    from repro.runtime.master import Master

    sound = Master.push_back

    def push_back(self, m):
        if m.msg_id % 7:
            sound(self, m)

    monkeypatch.setattr(Master, "push_back", push_back)


def _order_broken(monkeypatch):
    from repro.runtime.master import Master

    def head(self, image):
        dq = self._img_queues.get(image)
        return dq[-1][1] if dq else None

    def pull(self, image):
        dq = self._img_queues.get(image)
        if not dq:
            return None
        _, m = dq.pop()
        self._qlen -= 1
        self.in_flight += 1
        if not dq:
            self._event(image).clear()
        return m

    monkeypatch.setattr(Master, "head", head)
    monkeypatch.setattr(Master, "pull", pull)


@pytest.mark.parametrize("fault", [_answer_altered, _answer_not_computed,
                                   _image_lost, _order_broken])
def test_stream_fault_is_not_correct(drive, monkeypatch, fault):
    cell = tiny_stream_cell()
    fault(monkeypatch)
    out = drive(cell)
    assert not _correct(out), out.checks
