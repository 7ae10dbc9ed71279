"""``flops_decoder.py`` against the hand count in its docstring."""

import os

import pytest

import common
import flops
import flops_decoder

CONFIG = common.load_json(os.path.join(
    common.BENCH_DIR, "configs", "kanana-2-30b-a3b.json"))


def test_a_position_is_the_hand_count():
    mla = 2 * (2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048)
    assert mla == 2 * 26_345_472
    dense = 6 * 2048 * 6144
    routed = 6 * 6 * 2048 * 768 + 6 * 2048 * 2 * 768 + 2 * 2048 * 128
    assert routed == 56_623_104 + 18_874_368 + 524_288
    assert flops_decoder.position_flops(CONFIG) == 7 * mla + dense + 6 * routed
    assert flops_decoder.position_flops(CONFIG) == pytest.approx(
        900.46e6, rel=1e-4)
    assert flops_decoder.pair_flops(CONFIG) == 7 * 2 * 32 * 320
    assert flops_decoder.head_flops(CONFIG) == 2 * 2048 * 128_256


def test_one_row_of_260_tokens_and_three_two_token_labels():
    step = {"rows": 1, "tokens_real": 260, "token_pairs": 260 * 261 // 2,
            "label_positions_real": 3}
    assert flops_decoder.step_counts(step) == {
        "positions": 263, "pairs": 33_930 + 3 * 260 + 6, "head_positions": 4}
    assert flops_decoder.step_flops(CONFIG, step) == pytest.approx(
        243.90e9, rel=1e-4)


def test_padding_does_not_enter_and_rows_add():
    one = {"rows": 1, "tokens_real": 100, "token_pairs": 5050,
           "label_positions_real": 3, "width": 1024}
    two = {"rows": 2, "tokens_real": 200, "token_pairs": 10100,
           "label_positions_real": 3, "width": 64}
    assert flops_decoder.step_flops(CONFIG, two) == pytest.approx(
        2 * flops_decoder.step_flops(CONFIG, one))


def test_a_full_step_is_compute_bound():
    # 32 rows of 290 real tokens: weights (8.9 GB) are read once a step
    step = {"rows": 32, "tokens_real": 32 * 290,
            "token_pairs": 32 * 290 * 291 // 2, "label_positions_real": 3}
    least = flops.roofline_seconds(
        flops_decoder.step_flops(CONFIG, step),
        flops_decoder.step_bytes(CONFIG, step), flops.load_peaks("TPU v5 lite"))
    assert least["bound"] == "compute"
    # every parameter but the embedding table (0.53 of the 8.86 GB), and
    # sixteen passes of the real positions' activations
    assert flops_decoder.step_bytes(CONFIG, step) == pytest.approx(
        8.862e9 - 0.525e9 + 16 * 32 * 293 * 2048 * 2, rel=0.002)
