"""``flops_jamba.py`` against the hand count in its head: one row of 320
prompt tokens and three two-token labels, at the published widths."""

import os

import pytest

import common
import flops_jamba

CONFIG = common.load_json(os.path.join(
    common.BENCH_DIR, "configs", "ai21-jamba2-3b.json"))

STEP = {"rows": 1, "width": 1024, "tokens_real": 320,
        "token_pairs": 320 * 321 // 2, "label_positions": 3,
        "label_positions_real": 3, "mamba1_layers": 26,
        "attention_layers": 2, "slots": 512}


def test_the_layers_of_the_sources_rule():
    # attention at 7 and 21 of 28 (offset 7 of period 14)
    assert flops_jamba._layers(CONFIG) == (26, 2)
    assert flops_jamba._widths(CONFIG) == (2560, 5120, 16, 160)


def test_a_position_a_pair_and_a_head_position():
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    assert mamba == 41_123_840
    assert flops_jamba.mamba_projection_flops(CONFIG) == 2 * mamba
    assert flops_jamba.recurrence_flops(CONFIG) == 7 * 5120 * 16 == 573_440
    attention = 2560 * 22 * 128 + 20 * 128 * 2560
    assert attention == 13_762_560
    assert flops_jamba.attention_projection_flops(CONFIG) == 2 * attention
    swiglu = 6 * 2560 * 8192
    want = 26 * (2 * mamba + 573_440) + 2 * 2 * attention + 28 * swiglu
    assert flops_jamba.position_flops(CONFIG) == want
    assert want == pytest.approx(5731.6e6, rel=1e-4)
    assert flops_jamba.pair_flops(CONFIG) == 2 * 2 * 20 * 256 == 20_480
    assert flops_jamba.head_flops(CONFIG) == 2 * 2560 * 65_536


def test_one_row_of_320_tokens_and_three_labels():
    counts = flops_jamba.step_counts(STEP)
    assert counts == {"positions": 323, "pairs": 52_326,
                      "head_positions": 4}
    want = 323 * 5731.61472e6 + 52_326 * 20_480 + 4 * 335.54432e6
    assert flops_jamba.step_flops(CONFIG, STEP) == pytest.approx(want)
    assert want == pytest.approx(1853.7e9, rel=1e-4)


def test_the_kernels_share_counts_the_prefills_real_tokens_alone():
    a_token_flops = 2 * 160 * 5120 + 7 * 5120 * 16
    assert flops_jamba.selective_scan_flops(CONFIG, STEP) == (
        320 * 26 * a_token_flops)
    a_token = 2 * 5120 * 2 + 160 * 2 + 2 * 16 * 2     # x y, r, B C
    a_call = 4 * 5120 * 16 + 2 * 4 * 5120 + 160 * 5120 * 2
    a_row = 5120 * 16 * 4                             # one float32 state
    assert flops_jamba.selective_scan_bytes(CONFIG, STEP) == (
        26 * (320 * a_token + a_call + a_row))
