"""The plain references against the program, on the CPU at a tiny size.

The OLMo reference must agree with ``DecoderLM`` prefill followed by
decode steps on the same parameters; the grouped-matmul reference with
the program's kernel run in interpret mode.  The references import
nothing of the program; only these tests put the two side by side."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import harness
from conftest import tiny_lm_cell
from reference import gmm as ref_gmm
from reference import olmo as ref_olmo


def test_olmo_reference_matches_prefill_and_decode():
    from repro.models import build_model, pad_vocab

    lm = harness.load_module(harness.HERE / "drivers" / "lm.py")
    cell = tiny_lm_cell()
    m = cell.config["model"]
    model = build_model(lm.arch(cell.config))
    w = lm.make_weights(cell.config, 11, pad_vocab(m["vocab_size"]))
    params = lm.program_params(model, w)
    P, G = 12, 6
    tokens = np.asarray(harness.rng(11, 0).integers(0, 1000, P + G), np.int32)
    with jax.default_matmul_precision("highest"):
        batch = lm.prompt_batch(tokens[None, :P])
        logits, cache = model.prefill(params, batch, max_len=P + G)
        got = [np.asarray(logits[0])]
        for t in range(P, P + G - 1):
            logits, cache = model.decode_step(
                params, {"tokens": jnp.asarray(tokens[None, t:t + 1])}, cache)
            got.append(np.asarray(logits[0]))
        want = ref_olmo.logits_at(w, {"rope_theta": m["rope_theta"]},
                                  tokens[:P + G - 1], P - 1, m["vocab_size"])
    got = np.stack(got)[:, : m["vocab_size"]]
    assert harness.max_scaled_error(got, want) < 1e-4


def test_served_gaps_are_zero_for_the_reference_argmax():
    lm = harness.load_module(harness.HERE / "drivers" / "lm.py")
    cell = tiny_lm_cell()
    m = cell.config["model"]
    w = lm.make_weights(cell.config, 5, 1024)
    cfg = {"rope_theta": m["rope_theta"]}
    prompt = np.arange(8, dtype=np.int32)
    served = []
    seq = list(prompt)
    with jax.default_matmul_precision("highest"):
        for _ in range(4):
            nxt = int(ref_olmo.logits_at(w, cfg, np.array(seq), len(seq) - 1,
                                         1024)[-1].argmax())
            served.append(nxt)
            seq.append(nxt)
    gaps = ref_olmo.served_gaps(w, cfg, prompt, np.array(served), 1024)
    assert gaps.shape == (4,) and np.all(gaps == 0)
    bad = ref_olmo.served_gaps(w, cfg, prompt, np.array(served[:3] + [5000]),
                               1024)
    assert np.isinf(bad[-1])


def test_gmm_reference_matches_the_kernel():
    from repro.kernels.grouped_matmul.ops import gmm

    r = harness.rng(3)
    x = jnp.asarray(r.standard_normal((2, 128, 256)), jnp.float32)
    w = jnp.asarray(r.standard_normal((2, 256, 256)), jnp.float32)
    sizes = jnp.asarray([128, 64], jnp.int32)
    with jax.default_matmul_precision("highest"):
        out = gmm(x, w, sizes, use_kernel=True, interpret=True)
    want = ref_gmm.grouped_matmul(x, w, sizes)
    assert harness.max_scaled_error(out, want) < 1e-5
    assert np.all(np.asarray(want)[1, 64:] == 0)
