"""``flops_ling.py`` against the hand count in its head: one row of 260
prompt tokens and three two-token labels, 2 of a position's 8 assignments
held here in every routed layer, at the published widths."""

import os

import pytest

import common
import flops_ling

CONFIG = common.load_json(os.path.join(
    common.BENCH_DIR, "configs", "ling-3.0-flash-vl.json"))

STEP = {"rows": 1, "width": 1024, "tokens_real": 260,
        "token_pairs": 260 * 261 // 2, "label_positions": 24,
        "label_positions_real": 3, "kda_layers": 6, "mla_layers": 1,
        "assignments": 260 * 6 * 8, "assignments_held": 260 * 6 * 2,
        "label_assignments_held": 3 * 6 * 2}


def test_the_layers_the_cut_keeps():
    # dense KDA layer 0, then published layers 6..11: five KDA, one MLA
    assert flops_ling._layers(CONFIG) == (6, 1, 1, 6)


def test_a_position_an_assignment_a_pair_and_a_head_position():
    kda = 5 * 2560 * 4096 + 4096 * 2560 + 2560 * 32
    assert kda == 62_996_480
    assert flops_ling.kda_projection_flops(CONFIG) == 2 * kda
    assert flops_ling.kda_recurrence_flops(CONFIG) == 32 * 6 * 128 * 128
    mla = (2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 32 * 128 * 2560
           + 2560 * 32)
    assert mla == 31_965_184
    assert flops_ling.mla_projection_flops(CONFIG) == 2 * mla
    a_routed_layer = 6 * 2560 * 768 + 2 * 2560 * 512   # shared, router of 512
    want = (6 * (2 * kda + 32 * 6 * 128 * 128) + 2 * mla
            + 6 * 2560 * 6144 + 6 * a_routed_layer)
    assert flops_ling.position_flops(CONFIG) == want
    assert want == pytest.approx(1019.64e6, rel=1e-4)
    assert flops_ling.assignment_flops(CONFIG) == 6 * 2560 * 768
    assert flops_ling.pair_flops(CONFIG) == 2 * 32 * 320 == 20_480
    assert flops_ling.head_flops(CONFIG) == 2 * 2560 * 39_296


def test_one_row_of_260_tokens_and_three_labels():
    counts = flops_ling.step_counts(STEP)
    assert counts == {"positions": 263, "assignments": 3156,
                      "pairs": 34_716, "head_positions": 4}
    want = (263 * 1019.641856e6 + 3156 * 11.79648e6 + 34_716 * 20_480
            + 4 * 201.19552e6)
    assert flops_ling.step_flops(CONFIG, STEP) == pytest.approx(want)
    assert want == pytest.approx(306.91e9, rel=1e-4)
    # a step that holds every expert a token chose costs the other six too
    everything = dict(STEP, assignments_held=260 * 6 * 8,
                      label_assignments_held=3 * 6 * 8)
    assert (flops_ling.step_flops(CONFIG, everything)
            - flops_ling.step_flops(CONFIG, STEP)) == pytest.approx(
                263 * 6 * 6 * 11.79648e6)


def test_the_kernels_share_counts_the_prefills_real_tokens_alone():
    assert flops_ling.kda_prefill_flops(CONFIG, STEP) == (
        260 * 6 * 32 * 6 * 128 * 128)
    a_token = 32 * (4 * 128 * 2 + 128 * 4 + 4)     # q k v o, g, beta
    a_row = 32 * 128 * 128 * 4                      # one float32 state
    assert flops_ling.kda_prefill_bytes(CONFIG, STEP) == (
        6 * (260 * a_token + a_row))
