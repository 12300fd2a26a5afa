"""The training step's FLOP count against a hand count."""

from __future__ import annotations

import flops_train
import harness

OLMO = harness.load_json(
    harness.HERE / "configs" / "olmo-1b-train.json")["model"]


def test_one_document():
    # 3 tokens: 6 FLOPs per matmul weight a token (1,073,741,824 in the
    # layers, 103,022,592 in the tied head), and causal attention over
    # 1 + 2 + 3 = 6 (query, key) pairs at 4 x 16 layers x 16 heads x 128
    # FLOPs a pair forward, three times that with the backward
    weights = 1_073_741_824 + 103_022_592
    pair = 4 * 16 * 16 * 128
    assert pair == 131_072
    assert flops_train.train_flops(OLMO, [3]) == 6 * 3 * weights + 3 * 6 * pair


def test_documents_attend_within_themselves():
    # a row packed with documents of 2 and 3 tokens sees 3 + 6 pairs, not
    # the 15 of one 5-token document
    one = flops_train.train_flops(OLMO, [5])
    two = flops_train.train_flops(OLMO, [2, 3])
    assert one - two == 3 * (15 - 9) * 131_072


def test_a_real_token_is_about_seven_gigaflops():
    # 7.06 GFLOP of matmuls a token, as the cell's sizing assumed
    per_token = flops_train.train_flops(OLMO, [1]) - 3 * 131_072
    assert per_token == 6 * (1_073_741_824 + 103_022_592) == 7_060_586_496
