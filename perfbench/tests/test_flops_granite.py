"""``flops_granite.py`` against the hand count in its head: one row of 260
prompt tokens and three two-token labels, 5 of a position's 10 assignments
held here in every layer, at the published widths."""

import os

import pytest

import common
import flops_granite

CONFIG = common.load_json(os.path.join(
    common.BENCH_DIR, "configs", "granite-4.0-h-small.json"))

STEP = {"rows": 1, "width": 1024, "tokens_real": 260,
        "token_pairs": 260 * 261 // 2, "label_positions": 3,
        "label_positions_real": 3, "ssm_layers": 9, "attention_layers": 1,
        "assignments": 260 * 10 * 10, "assignments_held": 260 * 10 * 5,
        "label_assignments_held": 3 * 10 * 5}


def test_the_layers_the_cut_keeps():
    # one whole period: five Mamba-2, one attention, four Mamba-2
    assert flops_granite._layers(CONFIG) == (9, 1)


def test_a_position_an_assignment_a_pair_and_a_head_position():
    mamba = 4096 * (2 * 8192 + 2 * 128 + 128) + 8192 * 4096
    assert mamba == 102_236_160
    assert flops_granite.mamba_projection_flops(CONFIG) == 2 * mamba
    scan = 128 * (64 * 257 + 4 * 128 * 64) + 128 * 257
    assert flops_granite.ssd_flops(CONFIG) == scan == 6_332_544
    # within 1% of the token-by-token recurrence's 6 N P a head
    assert scan == pytest.approx(128 * 6 * 128 * 64, rel=0.01)
    attention = 4096 * (32 + 16) * 128 + 32 * 128 * 4096
    assert attention == 41_943_040
    assert flops_granite.attention_projection_flops(CONFIG) == 2 * attention
    a_feed_forward = 6 * 4096 * 1536 + 2 * 4096 * 72     # shared, router of 72
    want = 9 * (2 * mamba + scan) + 2 * attention + 10 * a_feed_forward
    assert flops_granite.position_flops(CONFIG) == want
    assert want == pytest.approx(2364.5e6, rel=1e-4)
    assert flops_granite.assignment_flops(CONFIG) == 6 * 4096 * 768
    assert flops_granite.pair_flops(CONFIG) == 2 * 32 * 256 == 16_384
    assert flops_granite.head_flops(CONFIG) == 2 * 4096 * 50_176


def test_one_row_of_260_tokens_and_three_labels():
    counts = flops_granite.step_counts(STEP)
    assert counts == {"positions": 263, "assignments": 13_150,
                      "pairs": 34_716, "head_positions": 4}
    want = (263 * 2364.515456e6 + 13_150 * 18.874368e6 + 34_716 * 16_384
            + 4 * 411.041792e6)
    assert flops_granite.step_flops(CONFIG, STEP) == pytest.approx(want)
    assert want == pytest.approx(872.28e9, rel=1e-4)
    # a step that holds every expert a token chose costs the other five too
    everything = dict(STEP, assignments_held=260 * 10 * 10,
                      label_assignments_held=3 * 10 * 10)
    assert (flops_granite.step_flops(CONFIG, everything)
            - flops_granite.step_flops(CONFIG, STEP)) == pytest.approx(
                263 * 10 * 5 * 18.874368e6)


def test_the_kernels_share_counts_the_prefills_real_tokens_alone():
    assert flops_granite.ssd_prefill_flops(CONFIG, STEP) == (
        260 * 9 * 6_332_544)
    a_token = 2 * 8192 * 2 + 2 * 128 * 2 + 4 * 128    # x y, B C, delta
    a_row = 8192 * 128 * 4                             # one float32 state
    assert flops_granite.ssd_prefill_bytes(CONFIG, STEP) == (
        9 * (260 * a_token + a_row))
