"""``flops_sdar.py`` against the hand count in its head: one row of 260
prompt tokens, 4 blocks of 4 denoised in 4 passes each, at the published
widths."""

import os

import pytest

import common
import flops_sdar

CONFIG = common.load_json(os.path.join(
    common.BENCH_DIR, "configs", "sdar-30b-a3b-chat.json"))

STEP = {"rows": 1, "width": 1024, "tokens_real": 260, "tokens_prefilled": 260,
        "token_pairs": 16 * 65 * 66 // 2, "block_length": 4, "gen_blocks": 4,
        "denoise_passes": 16, "commit_passes": 4,
        "pass_pairs": 5 * 4 * (264 + 268 + 272 + 276),
        "positions_masked": 4 * (4 + 3 + 2 + 1)}


def test_a_position_a_pair_and_a_head_position():
    attention = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert attention == 18_874_368
    experts = 8 * 6 * 2048 * 768 + 2 * 2048 * 128
    assert flops_sdar.position_flops(CONFIG) == 7 * (2 * attention + experts)
    assert flops_sdar.position_flops(CONFIG) == pytest.approx(796.39e6, rel=1e-4)
    assert flops_sdar.pair_flops(CONFIG) == 7 * 2 * 32 * 256 == 114_688
    assert flops_sdar.head_flops(CONFIG) == 2 * 2048 * 151_936


def test_one_row_of_260_tokens_and_four_blocks():
    counts = flops_sdar.step_counts(STEP)
    assert counts == {"positions": 260 + 4 * 5 * 4, "pairs": 34_320 + 21_600,
                      "head_positions": 40}
    want = 340 * 796.393472e6 + 55_920 * 114_688 + 40 * 622.329856e6
    assert flops_sdar.step_flops(CONFIG, STEP) == pytest.approx(want)
    assert want == pytest.approx(302.08e9, rel=1e-4)


def test_least_bytes_read_the_weights_once_a_pass():
    layer = 18_874_368 + 2048 * 128 + 3 * 2048 * 768 * 128
    weights = 21 * 7 * layer + 16 * 2048 * 151_936
    cached = (21_600 // 4) * 2 * 4 * 128 * 7
    activations = (7 * 2 + 2) * 340 * 2048
    assert flops_sdar.step_bytes(CONFIG, STEP) == pytest.approx(
        2 * (weights + cached + activations))


def test_the_kernels_share_counts_the_prefills_real_pairs_alone():
    assert flops_sdar.block_causal_attention_flops(CONFIG, STEP) == (
        34_320 * 114_688)
    assert flops_sdar.block_causal_attention_bytes(CONFIG, STEP) == (
        7 * 260 * 2 * (32 + 4) * 128 * 2)
