"""The ``laguna`` decoder kinds (grouped-query attention layers of two
kinds in one model: full layers with YaRN on half the head, sliding-window
layers with more query heads and plain RoPE; QK-norm; one softplus gate a
head on the attention output; a leading dense layer, then sigmoid-routed
experts of which this chip holds a share, one shared expert) against the
plain float32 reference ``perfbench/reference/laguna_f32.py``, at the
``laguna-tiny`` size with seeded weights.

No modelling code for ``model_type: laguna`` is on this machine, so the
reference is held to what IS here: its YaRN to ``transformers``'
``_compute_yarn_parameters`` and its window to ``sliding_window_overlay``.
The kernel is held to the masked XLA form at windows around a tile's edge.
The end-to-end tests run the system as it is served, bfloat16, the kernel
under the interpreter, and hold it to ``TEST_TOLERANCE``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(REPO, "perfbench") not in sys.path:
    sys.path.insert(0, os.path.join(REPO, "perfbench"))

from reference import laguna_f32 as ref  # noqa: E402

from music_analyst_tpu.models import layers, llama  # noqa: E402
from music_analyst_tpu.models.layers import (  # noqa: E402
    MultiHeadAttention,
    causal_mask,
)
from music_analyst_tpu.models.llama import (  # noqa: E402
    PRESETS,
    AttentionKind,
    LlamaConfig,
    init_caches,
)
from music_analyst_tpu.models.moe import (  # noqa: E402
    RoutedMoE,
    compact_capacity,
    route_sigmoid_noaux,
)
from music_analyst_tpu.ops import flash_attention as fa  # noqa: E402
from music_analyst_tpu.ops.kv_cache import (  # noqa: E402
    BlockCausalPrefill,
    KVCache,
    prefill_tile_pairs,
)


def _preset(name):
    path = os.path.join(REPO, "music_analyst_tpu", "models", "presets",
                        name + ".json")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def _as_reference_config(preset: dict) -> dict:
    """A preset in the layout of a ``perfbench/configs`` file: what the
    source does not state sits under ``model``."""
    return {**preset,
            "model": {"experts_held": preset["runtime"]["experts_held"]}}


HF = _as_reference_config(_preset("laguna-tiny"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

_WORDS = ("love rain night baby tears dance road fire cold heart sun blue "
          "you me the and never always gone stay").split()


def _lyrics(seed: int, rows: int, longest: int = 400):
    rng = np.random.default_rng(seed)
    return [" ".join(rng.choice(_WORDS, size=int(n)))
            for n in rng.integers(5, longest, size=rows)]


@pytest.fixture(scope="module")
def clf():
    from music_analyst_tpu.engines.sentiment import get_backend

    return get_backend("laguna-tiny")


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


# ------------------------------------------------------------ configuration

def test_presets_are_built_from_their_files(clf):
    cfg = clf.config
    assert cfg.layer_types == ("full_attention",) + (
        "sliding_attention",) * 3 + ("full_attention",)
    kinds = dict(cfg.attention_kinds)
    assert kinds["full_attention"].n_heads == 4
    assert kinds["full_attention"].rotary_dim == 8
    assert dict(kinds["full_attention"].yarn)["factor"] == 4
    assert kinds["sliding_attention"] == AttentionKind(
        6, window=8, rope_theta=10_000.0)
    assert [cfg.mixer(i) for i in range(5)] == ["gqa"] * 5
    assert [cfg.routed_layer(i) for i in range(5)] == [False] + [True] * 4
    assert (cfg.window_layers, cfg.compact_stream, cfg.mixed_layers) == (
        3, True, True)
    assert (cfg.moe_router, cfg.gqa_output_gate, cfg.qk_norm) == (
        "sigmoid_noaux", "softplus", True)
    assert cfg.experts_held == (0, 4) and cfg.n_experts == 8
    attention = clf.params["layer_1"]["attention"]
    assert attention["q_proj"]["kernel"].shape == (64, 6, 16)
    assert attention["g_proj"].shape == (64, 6)
    assert attention["g_proj"].dtype == jnp.float32
    assert clf.params["layer_0"]["attention"]["q_proj"]["kernel"].shape == (
        64, 4, 16)
    assert "feed_forward" in clf.params["layer_0"]
    assert clf.params["layer_4"]["feed_forward_moe"]["router"].shape == (
        64, 8)
    big = PRESETS["laguna-s-2.1"]()
    kinds = dict(big.attention_kinds)
    assert (kinds["full_attention"].n_heads,
            kinds["sliding_attention"].n_heads) == (48, 72)
    assert kinds["sliding_attention"].window == 512
    assert kinds["full_attention"].rotary_dim == 64
    assert big.max_seq_len == 1_048_576 > layers.ROPE_TABLE_POSITIONS


def test_published_keys_are_the_catalogs_but_for_the_cut():
    """The configuration file carries every key of the catalog's row; the
    keys that differ are the ones ``reduced`` lists."""
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Laguna-S-2.1")
    with open(os.path.join(REPO, "perfbench", "configs",
                           "laguna-s-2.1.json"), encoding="utf-8") as fh:
        config = json.load(fh)
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        entry = next(c for c in json.load(fh)["configs"]
                     if c["name"] == "laguna-s-2.1")
    assert config["source"] == row["source_url"] == entry["source"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(entry["reduced"]) - {"corpus"}
    for key in differs:
        assert config["published"][key] == row["config"][key]
    n = config["num_hidden_layers"]
    assert config["layer_types"] == row["config"]["layer_types"][:n]
    preset = _preset("laguna-s-2.1")
    for key in ("mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert preset[key] == row["config"][key][:n]
    assert preset["num_experts"] == 256 and config["num_experts"] == 128


def test_the_files_parameter_count_is_the_presets():
    """``deployment.parameters`` of the configuration file against
    ``jax.eval_shape`` of the preset's parameter tree."""
    with open(os.path.join(REPO, "perfbench", "configs",
                           "laguna-s-2.1.json"), encoding="utf-8") as fh:
        stated = json.load(fh)["deployment"]
    config = PRESETS["laguna-s-2.1"]()
    shapes = jax.eval_shape(lambda: llama.init_params_by_layer(config))

    def count(tree):
        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(tree))

    parameters = stated["parameters"]
    assert count(shapes) == parameters["total"] == 5_572_078_848
    assert count(shapes["layer_0"]) == parameters["layer_0"]
    assert count(shapes["layer_1"]) == parameters["routed_sliding_layer"]
    assert count(shapes["layer_4"]) == parameters["routed_full_layer"]
    assert count(shapes["layer_1"]["attention"]) == parameters[
        "sliding_attention_mixer"]
    assert count(shapes["layer_4"]["attention"]) == parameters[
        "full_attention_mixer"]
    assert sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(shapes)
               ) == stated["bytes"]


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True),
    ("moe_router_logit_softcapping", 30.0),
    ("moe_apply_router_weight_on_input", True),
    ("decoder_sparse_step", 2),
    ("mlp_only_layers", [1]),
    ("layer_types", ["full_attention", "chunked_attention"] + [
        "sliding_attention"] * 3),
    ("gating", True),
    ("gating_types", ["per_head"] * 4 + ["per_element"]),
    ("num_attention_heads_per_layer", [4, 6, 6, 4, 4]),
    ("rope_parameters", {
        "full_attention": {"rope_type": "llama3", "rope_theta": 1e4},
        "sliding_attention": {"rope_type": "default", "rope_theta": 1e4}}),
    ("sliding_window", None),
    ("tie_word_embeddings", True),
    ("shared_expert_intermediate_size", 48),
])
def test_from_hf_config_refuses_by_name_what_it_cannot_run(key, value):
    hf = {**_preset("laguna-tiny"), key: value}
    with pytest.raises(ValueError, match=key):
        LlamaConfig.from_hf_config(hf, **hf["runtime"])


@pytest.mark.parametrize("reading", ["moe_router", "gqa_output_gate",
                                     "qk_norm", "shared_expert_gate"])
def test_the_four_readings_of_function_are_the_presets_to_state(reading):
    hf = _preset("laguna-tiny")
    runtime = {k: v for k, v in hf["runtime"].items() if k != reading}
    with pytest.raises(ValueError, match=reading):
        LlamaConfig.from_hf_config(hf, **runtime)


def test_a_gated_shared_expert_is_refused_not_approximated():
    hf = _preset("laguna-tiny")
    with pytest.raises(ValueError, match="shared_expert_gate"):
        LlamaConfig.from_hf_config(
            hf, **{**hf["runtime"], "shared_expert_gate": True})


def test_layer_types_says_which_kinds_it_knows():
    with pytest.raises(ValueError, match="sliding_attention"):
        dataclasses.replace(PRESETS["laguna-tiny"](),
                            layer_types=("linear_attention",) * 5)
    with pytest.raises(ValueError, match="attention_kinds"):
        dataclasses.replace(
            LlamaConfig.tiny(),
            attention_kinds=(("sliding_attention", AttentionKind(8)),))


def test_sdar_moe_still_refuses_a_sliding_window():
    """``_from_sdar_moe`` keeps its refusal: a block-causal rule with a
    window is untested."""
    hf = {**_preset("sdar-tiny"), "use_sliding_window": True}
    with pytest.raises(ValueError, match="use_sliding_window"):
        LlamaConfig.from_hf_config(hf, **hf["runtime"])


# --------------------------------------------------- RoPE: YaRN, partial

PUBLISHED_YARN = {
    "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
    "original_max_position_embeddings": 8192, "beta_slow": 1,
    "beta_fast": 32, "attention_factor": 1.4852030263919618,
    "partial_rotary_factor": 0.5}


@pytest.mark.parametrize("group,head_dim", [
    (PUBLISHED_YARN, 128),
    (HF["rope_parameters"]["full_attention"], 16),
    ({**PUBLISHED_YARN, "attention_factor": None, "factor": 8}, 128),
], ids=["published", "tiny", "no-attention-factor"])
def test_references_yarn_is_transformers(group, head_dim):
    """The reference's frequencies and factor against
    ``_compute_yarn_parameters`` with ``dim`` = the rotary part; and the
    program's own (``layers.rope_inverse_frequencies``) against both."""
    pytest.importorskip("torch")
    from transformers import PretrainedConfig
    from transformers.modeling_rope_utils import _compute_yarn_parameters

    scaling = {k: v for k, v in group.items()
               if k not in ("rope_theta", "partial_rotary_factor")
               and v is not None}
    config = PretrainedConfig(
        rope_theta=group["rope_theta"], head_dim=head_dim,
        partial_rotary_factor=group["partial_rotary_factor"],
        hidden_size=head_dim * 4, num_attention_heads=4,
        max_position_embeddings=1_048_576, rope_scaling=scaling)
    want, want_factor = _compute_yarn_parameters(config, "cpu")
    dim = int(head_dim * group["partial_rotary_factor"])
    group = {k: v for k, v in group.items() if v is not None}
    got, factor = ref.yarn_frequencies(group, dim)
    assert got.shape == (dim // 2,)
    np.testing.assert_allclose(got, want.numpy(), rtol=2e-6)
    assert factor == pytest.approx(want_factor, rel=1e-12)
    yarn = tuple(sorted((k, v) for k, v in group.items() if k not in (
        "rope_type", "rope_theta", "partial_rotary_factor")))
    ours, our_factor = layers.rope_inverse_frequencies(
        head_dim, float(group["rope_theta"]), dim, yarn)
    np.testing.assert_allclose(np.asarray(ours), want.numpy(), rtol=2e-6)
    assert our_factor == pytest.approx(want_factor, rel=1e-12)


def test_rope_turns_the_rotary_part_and_passes_the_rest():
    """``apply_rope`` with tables narrower than the head against the
    reference's ``rope`` (YaRN on half the head), with and without a
    table; at position 0 nothing turns but the factor scales."""
    group = HF["rope_parameters"]["full_attention"]
    yarn = tuple(sorted((k, v) for k, v in group.items() if k not in (
        "rope_type", "rope_theta", "partial_rotary_factor")))
    x = jax.random.normal(jax.random.key(0), (2, 24, 3, 16), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(24) * 7, (2, 24))
    want = ref.rope(x, positions, group)
    cos, sin = layers.rope_frequencies(16, 256, 500000.0, 8, yarn)
    assert cos.shape == (256, 4)
    got = layers.apply_rope(x, cos, sin, positions)
    np.testing.assert_allclose(got, want, atol=2e-5)
    cos, sin = layers.rope_at(positions, 16, 500000.0, 8, yarn)
    np.testing.assert_allclose(layers.apply_rope(x, cos, sin, None), want,
                               atol=2e-5)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    factor = group["attention_factor"]
    np.testing.assert_allclose(got[:, 0, :, :8], x[:, 0, :, :8] * factor,
                               rtol=1e-6)


def test_more_positions_than_a_table_holds_are_computed_not_tabled():
    """Past ``ROPE_TABLE_POSITIONS`` the layer computes the angles from
    the positions it is given: the same values, and no table in the
    program."""
    x = jax.random.normal(jax.random.key(1), (1, 8, 32), jnp.float32)
    positions = jnp.arange(8)[None] + 1000

    def run(max_positions):
        layer = MultiHeadAttention(
            n_heads=4, n_kv_heads=2, head_dim=8, use_rope=True,
            max_positions=max_positions, dtype=jnp.float32)
        params = layer.init(jax.random.key(0), x, causal_mask(8, 8, 0),
                            positions)
        fn = jax.jit(lambda p: layer.apply(p, x, causal_mask(8, 8, 0),
                                           positions))
        return fn(params), fn.lower(params).as_text()

    tabled, tabled_text = run(2048)
    computed, computed_text = run(2 * layers.ROPE_TABLE_POSITIONS)
    np.testing.assert_allclose(computed, tabled, atol=1e-5)
    assert "2048x4xf32" in tabled_text
    assert f"{2 * layers.ROPE_TABLE_POSITIONS}x4xf32" not in computed_text


# ----------------------------------------------------------- the window

def test_references_window_is_transformers_overlay():
    pytest.importorskip("torch")
    from transformers.masking_utils import sliding_window_overlay

    for window in (1, 8, 11, 64):
        overlay = sliding_window_overlay(window)
        want = np.asarray([[overlay(0, 0, q, k) and k <= q
                            for k in range(24)] for q in range(24)])
        np.testing.assert_array_equal(
            np.asarray(ref.attention_mask(24, window)), want)
        ours = layers.window_mask(causal_mask(24, 24, 0), window, 24, 24)
        np.testing.assert_array_equal(np.asarray(ours[0, 0]), want)
    np.testing.assert_array_equal(
        np.asarray(ref.attention_mask(24, 0)),
        np.asarray(causal_mask(24, 24, 0)[0, 0]))


def _masked_attention(q, k, v, lengths, window):
    """The masked XLA form the cache's causal view takes off the kernel."""
    view = BlockCausalPrefill(
        KVCache.zeros(q.shape[0], q.shape[1], k.shape[2], q.shape[3],
                      jnp.float32),
        lengths, 1, kernel=False, window=window).update(k, v)
    return view.attend(q)


@pytest.mark.parametrize("window", [8, 256, 257, 2000],
                         ids=["8", "tile", "tile+1", "longer-than-the-row"])
@pytest.mark.parametrize("heads", [9, 6], ids=["groups-of-9", "groups-of-6"])
def test_windowed_kernel_is_the_masked_form(window, heads):
    """The flash kernel with a window (interpreted) against the masked XLA
    form on every real position: windows of 8, of a tile, of a tile + 1
    and longer than the row; one key/value head to 9 and to 6 query
    heads; rows shorter than a tile, longer than the window, and full."""
    rows, width, d = 3, 512, 16
    lengths = jnp.asarray([512, 77, 300], jnp.int32)
    keys = jax.random.split(jax.random.key(window + heads), 3)
    q = jax.random.normal(keys[0], (rows, width, heads, d), jnp.float32)
    k = jax.random.normal(keys[1], (rows, width, 1, d), jnp.float32)
    v = jax.random.normal(keys[2], (rows, width, 1, d), jnp.float32)
    view = BlockCausalPrefill(
        KVCache.zeros(rows, width, 1, d, jnp.float32), lengths, 1,
        window=window).update(k, v)
    got = view.attend(q)
    want = _masked_attention(q, k, v, lengths, window)
    real = np.asarray(jnp.arange(width)[None, :] < lengths[:, None])
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real],
                               atol=2e-5)
    if window == 2000:  # a window longer than the row is the causal rule
        causal = BlockCausalPrefill(
            KVCache.zeros(rows, width, 1, d, jnp.float32), lengths, 1,
        ).update(k, v).attend(q)
        np.testing.assert_array_equal(np.asarray(got)[real],
                                      np.asarray(causal)[real])


@pytest.mark.parametrize("window", [0, 8, 128, 129, 256, 700])
@pytest.mark.parametrize("tile", [128, 256])
def test_the_tiles_the_kernel_says_it_visits_are_the_tiles_a_mask_needs(
        window, tile):
    """``visited_pairs`` (the kernel's own ``tile_runs`` over its grid)
    against the tiles that hold at least one pair of the mask."""
    width = 1024
    lengths = np.asarray([1024, 1, 129, 513, 770, 256])
    i, j = np.arange(width)[:, None], np.arange(width)[None, :]
    needed = 0
    for n in lengths:
        mask = (j <= i) & (j < n) & (i < n)
        if window:
            mask &= j > i - window
        tiles = mask.reshape(width // tile, tile, width // tile, tile)
        needed += int(tiles.any(axis=(1, 3)).sum()) * tile * tile
    assert fa.visited_pairs(lengths, width, tile, tile, window) == needed
    if window == 0:
        assert fa.visited_pairs(lengths, width, tile, tile) == needed


def test_prefill_tile_pairs_follows_the_views_tile():
    lengths = np.asarray([770, 300])
    # 1,024 wide: tiles of 512; a row of 770 runs three, one of 300 one
    assert prefill_tile_pairs(lengths, 1024) == 4 * 512 * 512
    # no key tile of 512 lies wholly behind a window of 512
    assert prefill_tile_pairs(lengths, 1024, 512) == 4 * 512 * 512
    # ... nor behind one of 8 (queries 512..518 see keys 505..511); a
    # window of 1 (a query sees itself) leaves the diagonal's tiles
    assert prefill_tile_pairs(lengths, 1024, 8) == 4 * 512 * 512
    assert prefill_tile_pairs(lengths, 1024, 1) == 3 * 512 * 512
    # off the kernel's widths the masked form computes every pair
    assert prefill_tile_pairs(lengths, 96, 8) == 2 * 96 * 96


def test_a_window_without_an_attention_that_masks_it_is_refused():
    x = jnp.zeros((2, 16, 32), jnp.float32)
    layer = MultiHeadAttention(n_heads=4, head_dim=8, window=4,
                               dtype=jnp.float32)
    with pytest.raises(ValueError, match="whole-row"):
        layer.init(jax.random.key(0), x, None, None,
                   lengths=jnp.asarray([16, 3]))
    with pytest.raises(ValueError, match="causal mask"):
        layer.init(jax.random.key(0), x, None, None)


# -------------------------------------------------- the layer, in float32

def _attention_layer(clf, index, **kw):
    cfg = clf.config
    kind = cfg.attention_kind(index)
    return MultiHeadAttention(
        n_heads=kind.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.attn_head_dim, use_rope=True,
        rope_theta=kind.rope_theta, window=kind.window,
        rotary_dim=kind.rotary_dim, yarn=kind.yarn,
        output_gate=cfg.gqa_output_gate, max_positions=cfg.max_seq_len,
        qk_norm=True, norm_eps=cfg.rms_norm_eps, dtype=jnp.float32, **kw)


@pytest.mark.parametrize("index", [0, 1], ids=["full", "sliding"])
def test_attention_layer_of_each_kind_matches_reference(clf, index):
    """The program's module in float32 on the reference's own input: heads,
    QK-norm, the kind's RoPE, the window, the gate."""
    params = _f32(clf.params[f"layer_{index}"]["attention"])
    h = jax.random.normal(jax.random.key(index), (2, 40, 64), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(40), (2, 40))
    kind = HF["layer_types"][index]
    heads = HF["num_attention_heads_per_layer"][index]
    with jax.default_matmul_precision("highest"):
        want, keys, values = ref.attention(params, h, positions, HF, kind,
                                           heads)
        got, cache = _attention_layer(clf, index).apply(
            {"params": params}, h, causal_mask(40, 40, 0), positions,
            KVCache.zeros(2, 40, 2, 16, jnp.float32))
    np.testing.assert_allclose(got, want, atol=3e-5)
    np.testing.assert_allclose(cache.keys, keys, atol=3e-5)
    np.testing.assert_allclose(cache.values, values, atol=3e-5)
    for omit in ref.OMISSIONS:
        touched = not (omit == "window" and index == 0
                       or omit == "yarn_factor" and index == 1)
        with jax.default_matmul_precision("highest"):
            other = ref.attention(params, h, positions, HF, kind, heads,
                                  omit=(omit,))[0]
        assert bool(jnp.abs(other - want).max() > 1e-3) == touched


@pytest.mark.parametrize("gate,fn", [("softplus", jax.nn.softplus),
                                     ("sigmoid", jax.nn.sigmoid)])
def test_the_gate_scales_each_head_of_the_attention_output(gate, fn):
    """``output_gate``: ``o_h <- f(x W_g)_h o_h`` before ``o_proj``, with
    either nonlinearity the field admits (the second is the correction a
    word in a preset would make)."""
    x = jax.random.normal(jax.random.key(0), (2, 12, 32), jnp.float32)
    mask = causal_mask(12, 12, 0)
    gated = MultiHeadAttention(n_heads=4, n_kv_heads=2, head_dim=8,
                               output_gate=gate, dtype=jnp.float32)
    params = gated.init(jax.random.key(1), x, mask)["params"]
    assert params["g_proj"].shape == (32, 4)
    plain = MultiHeadAttention(n_heads=4, n_kv_heads=2, head_dim=8,
                               dtype=jnp.float32)
    bare = {k: v for k, v in params.items() if k != "g_proj"}
    o_proj = params["o_proj"]["kernel"]                       # [H, d, D]
    # the ungated layer's heads, recovered through an identity o_proj
    eye = jnp.eye(32).reshape(4, 8, 32)
    heads = plain.apply({"params": {**bare, "o_proj": {"kernel": eye}}},
                        x, mask).reshape(2, 12, 4, 8)
    scale = fn(x @ params["g_proj"])[..., None]
    want = jnp.einsum("bshd,hdf->bsf", heads * scale, o_proj)
    with jax.default_matmul_precision("highest"):
        got = gated.apply({"params": params}, x, mask)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_sigmoid_router_at_256_of_10_is_the_references():
    """``route_sigmoid_noaux`` at the published router's width against the
    reference's router (the choice, and the weights as a dense matrix)."""
    from reference.deepseek_v3_f32 import route

    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)
    p = {"router": jnp.asarray(rng.normal(size=(96, 256)) / 10, jnp.float32),
         "e_score_correction_bias": jnp.asarray(
             rng.normal(size=(256,)) * 0.01, jnp.float32)}
    keys = {"num_experts_per_tok": 10, "norm_topk_prob": True,
            "routed_scaling_factor": 2.5}
    with jax.default_matmul_precision("highest"):
        _, chosen, combine, _ = route(p, h, keys)
        ours, weights = route_sigmoid_noaux(
            h @ p["router"], p["e_score_correction_bias"], 10, 2.5)
    np.testing.assert_array_equal(np.sort(ours, -1), np.sort(chosen, -1))
    dense = jnp.zeros((64, 256)).at[jnp.arange(64)[:, None], ours].set(
        weights)
    np.testing.assert_allclose(dense, combine, atol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)


def _moe(cfg: LlamaConfig, held, dtype=jnp.float32):
    return RoutedMoE(
        cfg.n_experts, cfg.moe_hidden_dim, cfg.moe_top_k,
        n_shared=cfg.n_shared_experts,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob, router=cfg.moe_router,
        dtype=dtype, experts_held=held)


def test_two_shares_add_up_to_the_uncut_layer(clf):
    """The share test: what the two shares of 4 of 8 experts give, the
    shared expert counted once, is the uncut reference layer; and the
    program's layer on a share is the reference's on that share."""
    cfg = clf.config
    h = jax.random.normal(jax.random.key(3), (2, 24, 64), jnp.float32)
    whole_module = _moe(cfg, None)
    whole = _f32(whole_module.init(jax.random.key(4), h)["params"])

    def part(first):
        return {**whole, **{name: whole[name][first:first + 4] for name in (
            "gate_experts", "up_experts", "down_experts")}}

    with jax.default_matmul_precision("highest"):
        uncut, _, _ = ref.moe_ffn(whole, h, HF, (0, 8))
        low, _, _ = ref.moe_ffn(part(0), h, HF, (0, 4))
        high, _, _ = ref.moe_ffn(part(4), h, HF, (4, 4), shared=False)
        np.testing.assert_allclose(low + high, uncut, atol=2e-5)
        assert float(jnp.abs(low - uncut).max()) > 1e-2
        for first, params in ((0, part(0)), (4, part(4))):
            got = _moe(cfg, (first, 4)).apply({"params": params}, h)
            want, _, _ = ref.moe_ffn(params, h, HF, (first, 4))
            np.testing.assert_allclose(got, want, atol=3e-5)
        np.testing.assert_allclose(
            whole_module.apply({"params": whole}, h), uncut, atol=3e-5)


@pytest.mark.parametrize("tokens,stretches", [(48, 3), (40, 1), (24, 1)])
def test_a_held_share_sorts_whole_stretches_at_a_time(
        clf, monkeypatch, tokens, stretches):
    """A token set of whole ``HELD_CHUNK`` stretches goes through the held
    experts a stretch at a time; anything else (10,240, 12,288 and 14,336
    slots of the cell's step) at once.  The same result."""
    from music_analyst_tpu.models import moe

    cfg = clf.config
    h = jax.random.normal(jax.random.key(tokens), (1, tokens, 64),
                          jnp.float32)
    layer = _moe(cfg, (0, 4))
    params = layer.init(jax.random.key(0), h)
    want = layer.apply(params, h)
    monkeypatch.setattr(moe, "HELD_CHUNK", 16)
    calls = []
    grouped = moe.grouped_experts_held
    monkeypatch.setattr(
        moe, "grouped_experts_held",
        lambda xt, *rest: calls.append(xt.shape[0]) or grouped(xt, *rest))
    np.testing.assert_allclose(layer.apply(params, h), want, atol=1e-5)
    assert calls == [tokens // stretches]      # traced once, under the map


# ----------------------------------------------------------- end to end

def _system(clf, lyrics, probe=None):
    prepared = clf.prepare(lyrics)
    _, ids, lens = prepared
    if probe is not None:
        clf.probe_rows = np.asarray(probe, np.int32)
    handle = clf.launch(clf.transfer(prepared))
    scores = np.asarray(handle[1], np.float64)
    labels = clf.collect(handle)
    return np.asarray(ids), np.asarray(lens), scores, handle[2], labels


def _judged(clf, ids, lens, stats, variant="f32", rows=None, omit=()):
    tol = ref.TEST_TOLERANCE
    rows = np.arange(len(lens)) if rows is None else np.asarray(rows)
    prefer = ref.prefer_from_system(
        np.asarray(stats["chosen"])[:, rows],
        np.asarray(stats["chosen_labels"])[:, :, rows], lens[rows])
    return ref.label_scores(
        clf.params, HF, ids[rows], lens[rows], clf._label_ids,
        clf._label_lens, variant=variant, prefer=prefer,
        margin=tol["route_margin"], omit=omit)


PROBE = [0, 2, 3, 5, 7, 8, 11, 12]


@pytest.fixture(scope="module")
def compact_step(clf):
    """One 512-wide step of 13 rows on the compact stream (rows from 40 to
    over 400 tokens: all longer than the window of 8), judged once."""
    lyrics = _lyrics(1, 12, 400) + [""]
    ids, lens, scores, stats, labels = _system(clf, lyrics, PROBE)
    return ids, lens, scores, stats, labels, _judged(clf, ids, lens, stats)


def _held(judged, stats, lens):
    kept = {k: v[:, PROBE] for k, v in judged["kept"].items()}
    return ref.compare_kept(kept, stats["probe"], lens[PROBE])


def _assert_within_tolerance(scores, judged, held):
    tol = ref.TEST_TOLERANCE
    diff = np.abs(scores - judged["scores"])
    routing = judged["routing"]
    assert routing["wrong"] <= tol["wrong_choices"], routing
    assert np.median(diff) <= tol["label_score_median"]
    assert diff.max() <= tol["label_score_max"]
    for name in ref.KEPT_LIMITS:
        assert held[name] <= tol[name], held


def test_compact_prefill_and_label_passes_agree_with_the_full_forward(
        clf, compact_step):
    """The system's prompt prefill (the windowed kernel on queries, keys
    and values put back from the compact stream), every layer's key/value
    cache, and the three label continuations on them (a sliding layer's
    masked behind its window), against one plain forward a label over
    prompt + label tokens."""
    ids, lens, scores, stats, labels, judged = compact_step
    capacity = compact_capacity(int(lens.sum()), ids.size)
    assert ids.shape == (13, 512)
    assert llama.runs_compact(clf.config, ids.shape, capacity)
    _assert_within_tolerance(scores, judged, _held(judged, stats, lens))
    assert labels[-1] == "Neutral"                 # the empty lyric


def test_padded_prefill_agrees_with_the_full_forward(clf):
    """A narrow step (128 wide: the masked form of the view, padded rows)
    the same way."""
    lyrics = _lyrics(2, 12, 60) + [""]
    ids, lens, scores, stats, _ = _system(clf, lyrics, PROBE)
    capacity = compact_capacity(int(lens.sum()), ids.size)
    assert not llama.runs_compact(clf.config, ids.shape, capacity)
    judged = _judged(clf, ids, lens, stats)
    _assert_within_tolerance(scores, judged, _held(judged, stats, lens))


@pytest.mark.parametrize("variant,omit", [
    ("int8", ()), ("f32", ("window",)), ("f32", ("yarn_factor",)),
    ("f32", ("gate",))], ids=["int8", "causal-window", "no-yarn-factor",
                              "no-gate"])
def test_a_lower_precision_or_a_part_left_out_fails_the_tolerance(
        clf, compact_step, variant, omit):
    """The reference in int8, and the reference with the window layers run
    causal, without YaRN's factor or without the gate, against the same
    step: each fails at least one limit of the comparison."""
    tol = ref.TEST_TOLERANCE
    ids, lens, scores, stats, _, _ = compact_step
    judged = _judged(clf, ids, lens, stats, variant=variant, omit=omit)
    held = _held(judged, stats, lens)
    diff = np.abs(scores - judged["scores"])
    failed = [name for name in ref.KEPT_LIMITS if held[name] > tol[name]]
    if np.median(diff) > tol["label_score_median"]:
        failed.append("label_score_median")
    if judged["routing"]["wrong"] > tol["wrong_choices"]:
        failed.append("wrong_choices")
    assert failed, (held, float(np.median(diff)), judged["routing"])
    if omit:  # a part of the mathematics left out is a gross error
        assert {"keys_median", "values_median", "keys_max"} <= set(failed)


def test_a_row_is_unaffected_by_its_neighbours_and_by_fillers(clf):
    """One row's scores and caches alone, among other rows, and among
    other rows at another capacity (more fillers) are the same to the
    rounding of a different program."""
    lyrics = _lyrics(5, 6, 400)

    def of_row0(batch):
        _, _, scores, stats, _ = _system(clf, batch, [0] * 8)
        return scores[0], np.asarray(stats["probe"]["keys"][:, 0],
                                     np.float32)

    alone, keys_alone = of_row0(lyrics[:1] + [""] * 3)
    among, keys_among = of_row0(lyrics[:4])
    other, keys_other = of_row0([lyrics[0], lyrics[4], lyrics[5], ""])
    n = len(clf.tokenizer.encode(llama.zero_shot_prompt(lyrics[0]), 1024)[0])
    assert n > 8
    for scores, keys in ((among, keys_among), (other, keys_other)):
        np.testing.assert_allclose(scores, alone, atol=0.02)
        assert float(np.abs(keys - keys_alone).max()) <= 0.05 * float(
            np.abs(keys_alone).max())


def test_single_token_steps_through_the_caches_cross_the_window(clf):
    """Prefill, then eight teacher-forced single-token steps through every
    layer's cache (``generate_scan_program``'s call: slots behind the
    prompt's width, positions behind the row's length), logits against the
    reference's one full forward over prompt + the eight tokens.  Rows of
    3 and 5 tokens cross the window of 8 on the way."""
    cfg = clf.config
    rows, width, steps = 4, 64, 8
    lens = np.asarray([64, 3, 40, 5])
    rng = np.random.default_rng(5)
    ids = rng.integers(16, cfg.vocab_size, (rows, width)).astype(np.int32)
    forced = rng.integers(16, cfg.vocab_size, (rows, steps)).astype(np.int32)
    lens_d = jnp.asarray(lens, jnp.int32)
    total = width + steps
    mask = causal_mask(width, total, 0) & (
        jnp.arange(total)[None, None, None, :] < lens_d[:, None, None, None])
    positions = jnp.broadcast_to(jnp.arange(width), (rows, width))
    logits, caches = clf.model.apply(
        {"params": clf.params}, jnp.asarray(ids), positions, mask,
        init_caches(cfg, rows, total), last_position=lens_d - 1,
        prefill_lengths=lens_d, row_lengths=lens_d)
    caches = [c.with_length(width) for c in caches]
    key_positions = llama._key_positions(cfg, lens_d, width, total)
    assert key_positions.shape == (rows, total)
    got = [np.asarray(logits[:, 0])]
    for t in range(steps):
        kv_pos = jnp.arange(total)[None, None, None, :]
        seen = (kv_pos < lens_d[:, None, None, None]) | (
            (kv_pos >= width) & (kv_pos - width <= t))
        logits, caches = clf.model.apply(
            {"params": clf.params}, jnp.asarray(forced[:, t:t + 1]),
            (lens_d + t)[:, None], seen, caches,
            key_positions=key_positions)
        got.append(np.asarray(logits[:, 0]))
    got = np.stack(got[:-1], axis=1)                       # [R, steps, V]
    sequences = np.zeros((rows, total), np.int32)
    for r, n in enumerate(lens):
        sequences[r, :n] = ids[r, :n]
        sequences[r, n:n + steps] = forced[r]
    read_at = (lens[:, None] - 1) + np.arange(steps)[None, :]
    want = ref.forward(clf.params, HF, sequences, read_at)["logits"]
    scale = float(np.abs(want).max())
    assert float(np.median(np.abs(got - want))) < 0.02 * scale
    # a (row, step)'s largest error; a router's tie that rounding broke the
    # other way shows in single steps (one of 32 read 0.2 of the scale, the
    # rest under 0.06), so the limit is on most of them, not on the worst
    worst = np.abs(got - want).max(axis=-1)
    assert float(np.quantile(worst, 0.85)) < 0.05 * scale, worst
    # without the keys' positions a step's window counts slots, not
    # positions: a row shorter than the step's width loses its prompt
    wrong, _ = clf.model.apply(
        {"params": clf.params}, jnp.asarray(forced[:, -1:]),
        (lens_d + steps - 1)[:, None], seen, caches)
    off = np.abs(np.asarray(wrong[:, 0]) - want[:, -1]).max(axis=-1)
    assert float(off[1]) > 0.15 * scale and float(off[3]) > 0.15 * scale


def test_generation_masks_what_lies_behind_the_window(clf):
    """``generate_batch`` (prefill, then a token a step through every
    layer's cache in one scan) gives the tokens of the explicit step
    loop."""
    prompts = ["love rain night", "the sun never stays gone baby " * 6]
    batch = clf.generate_batch(prompts, max_new_tokens=6, early_exit=False)
    assert batch == clf.generate_batch(prompts, max_new_tokens=6)
    alone = clf.generate(prompts[0], max_new_tokens=6)
    assert batch[0].split()[:2] == alone.split()[:2]
    assert len(batch[0].split()) == len(alone.split()) == 6


def test_defaults_are_the_program_it_was():
    """No window, no rotary part, no YaRN and no gate lower to the text the
    configuration lowers to without the fields; each of them set changes
    it."""
    base = LlamaConfig.tiny()

    def lowered(cfg):
        model = llama.LlamaModel(cfg)
        ids = jnp.zeros((2, 16), jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(16), (2, 16))
        params = jax.eval_shape(
            model.init, jax.random.key(0), ids, positions,
            causal_mask(16, 16, 0))["params"]
        text = jax.jit(lambda p: model.apply(
            {"params": p}, ids, positions, causal_mask(16, 16, 0),
            init_caches(cfg, 2, 16))).lower(params).as_text()
        return hashlib.sha256(text.encode()).hexdigest(), params

    plain, params = lowered(base)
    assert "g_proj" not in params["layer_0"]["attention"]
    listed = dataclasses.replace(base, layer_types=("full_attention",) * 2)
    assert lowered(listed)[0] == plain
    same = dataclasses.replace(listed, attention_kinds=(
        ("full_attention", AttentionKind(base.n_heads, 0, base.rope_theta)),))
    assert lowered(same)[0] == plain
    yarn = tuple(sorted(PUBLISHED_YARN.items()))
    for kind in (AttentionKind(base.n_heads, 4, base.rope_theta),
                 AttentionKind(base.n_heads, 0, base.rope_theta, 8),
                 AttentionKind(base.n_heads, 0, base.rope_theta, 0, yarn),
                 AttentionKind(4, 0, base.rope_theta)):
        changed = dataclasses.replace(listed, attention_kinds=(
            ("full_attention", kind),))
        assert lowered(changed)[0] != plain
    gated, gated_params = lowered(
        dataclasses.replace(base, gqa_output_gate="softplus"))
    assert gated != plain
    assert gated_params["layer_0"]["attention"]["g_proj"].shape == (128, 8)


def test_decode_runtimes_refuse_the_window_not_a_cache_it_does_not_have(clf):
    from music_analyst_tpu.serving.decode_runtime import (
        decode_runtime_refusal,
        paged_runtime,
        slot_runtime,
    )

    refusal = decode_runtime_refusal(clf, "paged")
    assert "sliding window" in refusal and "paged" in refusal
    assert "latent" not in refusal and "recurrent" not in refusal
    for build in (slot_runtime, paged_runtime):
        with pytest.raises(NotImplementedError, match="sliding window"):
            build(clf)


def test_cli_writes_the_jobs_files_and_counts_what_a_step_did(tmp_path):
    from music_analyst_tpu.cli.main import main

    fixture = os.path.join(REPO, "tests", "fixtures", "mini_songs.csv")
    assert main(["sentiment", fixture, "--model", "laguna-tiny",
                 "--batch-size", "4", "--output-dir", str(tmp_path)]) == 0
    with open(tmp_path / "sentiment_totals.json", encoding="utf-8") as fh:
        assert sum(json.load(fh).values()) == 8
    with open(tmp_path / "run_manifest.json", encoding="utf-8") as fh:
        manifest = json.load(fh)
    counters, gauges = manifest["counters"], manifest["gauges"]
    prompt_tokens = counters["decoder.tokens_real"] - 8 * 3
    assert counters["attention.full_tokens"] == 2 * prompt_tokens
    assert counters["attention.window_tokens"] == 3 * prompt_tokens
    assert 0 < counters["moe.assignments_held"] < counters["moe.assignments"]
    for path in ("gqa.window", "gqa.output_gate", "rope.yarn",
                 "rope.partial", "moe.experts_held"):
        assert counters[f"traced.{path}"] > 0, path
    # 64-wide steps of 4 rows: the view's masked form, and the label
    # passes' windowed dense attention
    assert counters["attention.window_causal_dense"] > 0
    assert counters["attention.window_dense"] > 0
    # 4 rows x (64 + 8) keys x 5 layers x keys and values x 2 heads x 16 x 2
    assert gauges["kv_cache_bytes"] == 4 * 72 * 5 * 2 * 2 * 16 * 2
    assert "ssm.tokens" not in counters and "latent_cache_bytes" not in gauges
    spans = [s for s in manifest["spans"] if s.get("name") == "compute"] if (
        isinstance(manifest.get("spans"), list)) else []
    for span in spans:
        attrs = span.get("attrs", {})
        if "token_pairs_tiles" in attrs:
            assert attrs["token_pairs"] == (
                2 * attrs["token_pairs_full"]
                + 3 * attrs["token_pairs_window"])
            assert attrs["token_pairs_window"] < attrs["token_pairs_full"]
            assert attrs["token_pairs_tiles"] >= attrs["token_pairs"]
