"""``flops_laguna.py`` against the hand count in its head: one row of 700
prompt tokens and three two-token labels, 5 of a position's 10 assignments
held here in every routed layer, at the published widths."""

import os

import pytest

import common
import flops_laguna

CONFIG = common.load_json(os.path.join(
    common.BENCH_DIR, "configs", "laguna-s-2.1.json"))

WINDOWED = 512 * 513 // 2 + 188 * 512           # a row of 700, window 512
STEP = {"rows": 1, "width": 1024, "tokens_real": 700,
        "label_positions": 3, "label_positions_real": 3,
        "attention_layers_full": 2, "attention_layers_window": 3,
        "token_pairs_full": 700 * 701 // 2, "token_pairs_window": WINDOWED,
        "label_pairs_full": 3 * 701, "label_pairs_window": 3 * 512,
        "token_pairs": 2 * (700 * 701 // 2) + 3 * WINDOWED,
        "token_pairs_tiles": 5 * 3 * 512 * 512,
        "assignments": 700 * 10 * 4, "assignments_held": 700 * 5 * 4,
        "label_assignments_held": 3 * 5 * 4}


def test_the_layers_the_cut_keeps():
    # the leading dense full layer and one whole period behind it
    assert flops_laguna.kinds(CONFIG) == {
        "full_attention": (2, 48), "sliding_attention": (3, 72)}
    assert flops_laguna._ffn_layers(CONFIG) == (1, 4)
    # the lists that run a layer each stand whole in the file
    assert len(CONFIG["num_attention_heads_per_layer"]) == 48


def test_a_position_an_assignment_a_pair_and_a_head_position():
    full = 2 * (3072 * 64 * 128 + 48 * 128 * 3072) + 2 * 3072 * 48
    sliding = 2 * (3072 * 88 * 128 + 72 * 128 * 3072) + 2 * 3072 * 72
    assert (full, sliding) == (88_375_296, 126_271_488)
    assert flops_laguna.attention_projection_flops(CONFIG, 48) == full
    assert flops_laguna.attention_projection_flops(CONFIG, 72) == sliding
    dense = 6 * 3072 * 12288
    a_routed = 6 * 3072 * 1024 + 2 * 3072 * 256       # shared, router of 256
    want = 2 * full + 3 * sliding + dense + 4 * a_routed
    assert flops_laguna.position_flops(CONFIG) == want == 863_846_400
    assert flops_laguna.assignment_flops(CONFIG) == 6 * 3072 * 1024
    assert flops_laguna.pair_flops(CONFIG, 48) == 24_576
    assert flops_laguna.pair_flops(CONFIG, 72) == 36_864
    assert flops_laguna.head_flops(CONFIG) == 2 * 3072 * 50_176
    # the issue's count: half of a real token's operations are attention's
    attention = 2 * full + 3 * sliding + 366 * 2 * 24_576 + 328 * 3 * 36_864
    a_token = want + 4 * 5 * 18_874_368 + 366 * 2 * 24_576 + (
        328 * 3 * 36_864)
    assert attention / a_token == pytest.approx(0.47, abs=0.02)


def test_one_row_of_700_tokens_and_three_labels():
    assert WINDOWED == 227_584
    want = (703 * 863_846_400 + 14_060 * 18_874_368
            + 2 * 247_453 * 24_576 + 3 * 229_120 * 36_864
            + 4 * 308_281_344)
    assert flops_laguna.step_flops(CONFIG, STEP) == pytest.approx(want)
    assert want == pytest.approx(911.39e9, rel=1e-4)
    # a step that holds every expert a token chose costs the other five too
    everything = dict(STEP, assignments_held=700 * 10 * 4,
                      label_assignments_held=3 * 10 * 4)
    assert (flops_laguna.step_flops(CONFIG, everything)
            - flops_laguna.step_flops(CONFIG, STEP)) == pytest.approx(
                703 * 4 * 5 * 18_874_368)


def test_the_kernels_share_counts_the_prefills_real_pairs_alone():
    assert flops_laguna.attention_flops(CONFIG, STEP, labels=False) == (
        2 * 24_576 * (700 * 701 // 2) + 3 * 36_864 * WINDOWED)
    # q and o of 48 / 72 heads, k and v of 8, bfloat16, a real position
    a_token = 2 * (2 * 48 + 16) * 128 * 2 + 3 * (2 * 72 + 16) * 128 * 2
    assert flops_laguna.attention_prefill_bytes(CONFIG, STEP) == 700 * a_token
