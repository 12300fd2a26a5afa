"""The operation and byte counts against hand counts."""

from __future__ import annotations

import math

import flops
import harness

OLMO = harness.load_json(harness.HERE / "configs" / "olmo-1b.json")["model"]


def test_gmm_call_at_the_stream_payload_shape():
    c = flops.gmm_call(8, 512, 2048, 2048)
    assert c["flops"] == 2 * 8 * 512 * 2048 * 2048 == 34_359_738_368
    # x and the output 8 x 512 x 2048 each, w 8 x 2048 x 2048, float32
    assert c["bytes"] == 4 * (2 * 8_388_608 + 33_554_432) == 201_326_592


def test_olmo_matmul_weights():
    p = flops.decoder_matmul_params(OLMO)
    # q, k, v, o: 4 x 2048 x 2048; gate, up, down: 3 x 2048 x 8192
    assert p["per_layer"] == 16_777_216 + 50_331_648 == 67_108_864
    assert p["layers"] == 16 * 67_108_864 == 1_073_741_824
    assert p["unembed"] == 2048 * 50304 == 103_022_592


def test_olmo_matmul_weights_match_the_program_specs():
    from repro.models import abstract_params, build_model

    lm = harness.load_module(harness.HERE / "drivers" / "lm.py")
    specs = abstract_params(build_model(lm.arch({"model": OLMO})).param_specs())
    blocks = sum(math.prod(a.shape) for a in
                 __import__("jax").tree.leaves(specs["blocks"]))
    assert blocks == flops.decoder_matmul_params(OLMO)["layers"]


def test_olmo_decode_step():
    # one sequence whose new token sees 10 positions: every matmul weight
    # twice (multiply-add), and QK^T plus PV over 10 positions in 16
    # layers of 16 heads of 128
    per_pos = 4 * 16 * 16 * 128
    assert per_pos == 131_072
    assert flops.decode_step_flops(OLMO, 1, 10) == (
        2 * (1_073_741_824 + 103_022_592) + 10 * per_pos)
    assert flops.decode_step_flops(OLMO, 8, 10) == 8 * flops.decode_step_flops(
        OLMO, 1, 10)
    # bytes: every weight once in float32, K and V of 10 positions
    assert flops.decode_step_bytes(OLMO, 1, 10) == (
        4 * (1_073_741_824 + 103_022_592) + 4 * 2 * 16 * 16 * 128 * 10)


def test_olmo_prefill():
    # 4 positions: every position through the layers, causal attention
    # over 1 + 2 + 3 + 4 = 10 (query, key) pairs, logits of the last one
    assert flops.prefill_flops(OLMO, 1, 4) == (
        2 * 4 * 1_073_741_824 + 10 * 131_072 + 2 * 103_022_592)
    assert flops.prefill_flops(OLMO, 3, 4) == 3 * flops.prefill_flops(
        OLMO, 1, 4)
