"""``flops.py`` against the hand count, and the table of peaks."""

import pytest

import flops

DISTILBERT = {"dim": 768, "hidden_dim": 3072, "n_layers": 6, "n_classes": 2}


def test_per_token_is_the_hand_count():
    per_layer = 8 * 768 ** 2 + 4 * 768 * 3072 + 4 * 128 * 768
    assert per_layer == 14_548_992
    assert flops.encoder_flops_per_token(DISTILBERT, 128) == 6 * per_layer
    assert flops.encoder_flops_per_token(DISTILBERT, 128) == pytest.approx(
        87.3e6, rel=0.001)


def test_per_song_and_per_step():
    song = flops.encoder_step_flops(DISTILBERT, 1, 128)
    assert song == pytest.approx(11.2e9, rel=0.01)
    assert flops.encoder_step_flops(DISTILBERT, 4096, 128) == 4096 * song


def test_the_step_is_compute_bound_at_the_benchmarks_shape():
    peaks = flops.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    least = flops.roofline_seconds(
        flops.encoder_step_flops(DISTILBERT, 8192, 128),
        flops.encoder_step_bytes(DISTILBERT, 8192, 128), peaks)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(8192 * 11.2e9 / 197e12, rel=0.01)


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        flops.load_peaks("TPU v9 imaginary")
