"""``sdar-30b-a3b-chat``'s layer kinds and its generation by diffusion over
blocks at test size (``sdar-tiny``) on the CPU, seeded random weights,
against the plain float32 reference (``perfbench/reference/
sdar_moe_f32.py``): grouped-query attention with QK-norm and a head width
that is not ``dim / n_heads``, the softmax router, the block-causal forward,
every denoising and commit pass through the cache, the sampler on crafted
logits, the compact prefill, the configuration's keys, and the path through
``get_backend`` and the CLI.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

from reference import sdar_moe_f32 as ref  # noqa: E402

from music_analyst_tpu.models.block_diffusion import (  # noqa: E402
    BLOCK_STEP_REFUSAL,
    BlockDiffusionClassifier,
    unmask,
)
from music_analyst_tpu.models.layers import (  # noqa: E402
    MultiHeadAttention,
    block_causal_mask,
    padding_mask,
)
from music_analyst_tpu.models.llama import (  # noqa: E402
    LlamaConfig,
    LlamaModel,
)
from music_analyst_tpu.models.moe import (  # noqa: E402
    RoutedMoE,
    route_softmax_topk,
)
from music_analyst_tpu.ops.flash_attention import flash_attention  # noqa: E402
from music_analyst_tpu.ops.kv_cache import (  # noqa: E402
    BlockCausalPrefill,
    BlockPass,
    KVCache,
    block_causal_tile,
)

F32_TOL = 2e-4  # float32 program against float32 reference
SEEDS = (0, 1, 2)


def _preset(name):
    path = os.path.join(REPO, "music_analyst_tpu", "models", "presets",
                        name + ".json")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


HF = _preset("sdar-tiny")
SAMPLER = {key: HF["runtime"][key] for key in (
    "block_length", "denoising_steps", "confidence_threshold",
    "mask_token_id")}
PUBLISHED = _preset("sdar-30b-a3b-chat")

_WORDS = ["w%d" % i for i in range(500)]


def _lyrics(seed: int):
    """Eight seeded lyrics whose prompts end at every place in a block."""
    rng = np.random.default_rng(seed)
    return [" ".join(rng.choice(_WORDS, size=n))
            for n in (30, 55, 70, 18, 41, 64, 9, 77)]


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


@pytest.fixture(scope="module")
def steps():
    """``{seed: (backend, prompt_ids, prompt_lens, out, stats)}``: one step
    of eight rows through the timed path a seed, the caches kept."""
    from music_analyst_tpu.engines.sentiment import get_backend

    made = {}
    for seed in SEEDS:
        backend = get_backend("sdar-tiny", seed=seed)
        prepared = backend.prepare(_lyrics(seed))
        handle = backend.launch(backend.transfer(prepared), keep_caches=True)
        made[seed] = (backend, np.asarray(prepared[1]),
                      np.asarray(prepared[2]), handle[1], handle[2])
    return made


@pytest.fixture(scope="module")
def clf(steps):
    return steps[0][0]


# ----------------------------------------------------------- configuration

def test_preset_is_built_from_its_file(clf):
    cfg = clf.config
    assert isinstance(clf, BlockDiffusionClassifier)
    assert (cfg.attention, cfg.moe_router, cfg.generation) == (
        "gqa", "softmax_topk", "block_diffusion")
    assert cfg.qk_norm and cfg.head_dim == 32 != cfg.dim // cfg.n_heads
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.n_layers) == (4, 2, 3)
    assert all(cfg.routed_layer(i) for i in range(3))
    assert (cfg.n_experts, cfg.moe_top_k, cfg.n_shared_experts) == (8, 2, 0)
    assert (cfg.block_length, cfg.denoising_steps, cfg.mask_token_id) == (
        4, 4, 4095)
    # the mask token is no word's id
    assert clf.tokenizer.vocab_size == cfg.mask_token_id
    assert clf.gen_blocks == 4


def test_published_keys_are_the_catalogs_but_for_depth():
    catalog = {"attention_bias": False, "decoder_sparse_step": 1,
               "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
               "intermediate_size": 6144, "max_position_embeddings": 32768,
               "max_window_layers": 48, "mlp_only_layers": [],
               "model_type": "sdar_moe", "moe_intermediate_size": 768,
               "norm_topk_prob": True, "num_attention_heads": 32,
               "num_experts": 128, "num_experts_per_tok": 8,
               "num_hidden_layers": 48, "num_key_value_heads": 4,
               "rms_norm_eps": 1e-06, "rope_scaling": None,
               "rope_theta": 1000000, "sliding_window": None,
               "tie_word_embeddings": False, "use_sliding_window": False,
               "vocab_size": 151936}
    differs = {k for k, v in catalog.items() if PUBLISHED[k] != v}
    assert differs == {"num_hidden_layers"}
    cfg = LlamaConfig.from_hf_config(PUBLISHED, **PUBLISHED["runtime"])
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.attn_head_dim) == (
        2048, 32, 4, 128)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.moe_hidden_dim) == (128, 8, 768)
    assert (cfg.vocab_size, cfg.mask_token_id) == (151936, 151669)
    assert cfg.prompt_width_floor == 1024 and cfg.param_dtype == "bfloat16"


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True), ("mlp_only_layers", [0]),
    ("decoder_sparse_step", 2), ("rope_scaling", {"type": "yarn"}),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("hidden_act", "gelu"),
])
def test_from_hf_config_refuses_by_name_what_it_cannot_run(key, value):
    with pytest.raises(ValueError, match=key):
        LlamaConfig.from_hf_config({**HF, key: value}, **HF["runtime"])


def test_block_diffusion_needs_its_sampler_stated():
    with pytest.raises(ValueError, match="block_length"):
        LlamaConfig.from_hf_config(HF)  # no runtime section: no block length
    with pytest.raises(ValueError, match="mask_token_id"):
        LlamaConfig.from_hf_config(
            HF, **{**HF["runtime"], "mask_token_id": 4096})


# ------------------------------------------------- layers against reference

def test_attention_with_qk_norm_and_its_own_head_width():
    dim, heads, kv_heads, d, n_tok = 64, 4, 2, 32, 24
    attn = MultiHeadAttention(
        n_heads=heads, n_kv_heads=kv_heads, head_dim=d, use_rope=True,
        rope_theta=1e6, max_positions=64, dtype=jnp.float32, qk_norm=True,
        norm_eps=1e-6)
    h = jax.random.normal(jax.random.key(0), (1, n_tok, dim), jnp.float32)
    mask = block_causal_mask(n_tok, n_tok, 4)
    params = attn.init(jax.random.key(1), h, mask)["params"]
    # learned scales that are not 1, so the norm's scale is exercised
    params["q_norm"]["scale"] = jnp.linspace(0.5, 1.5, d)
    params["k_norm"]["scale"] = jnp.linspace(1.5, 0.5, d)
    assert params["q_proj"]["kernel"].shape == (dim, heads, d)
    got = attn.apply({"params": params}, h, mask)
    hf = {"num_attention_heads": heads, "num_key_value_heads": kv_heads,
          "head_dim": d, "rms_norm_eps": 1e-6, "rope_theta": 1e6}
    want, _, _ = ref.attention(params, h[0], jnp.arange(n_tok),
                               ref.block_causal(n_tok, 4), hf)
    np.testing.assert_allclose(got[0], want, atol=F32_TOL)


def test_softmax_router_is_softmax_then_top_k_renormalised():
    logits = jax.random.normal(jax.random.key(3), (50, 8)) * 3
    chosen, weights = route_softmax_topk(logits, 2)
    probs = np.asarray(jax.nn.softmax(logits, -1))
    order = np.argsort(-probs, -1)[:, :2]
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(order, -1))
    top = np.take_along_axis(probs, np.asarray(chosen), -1)
    np.testing.assert_allclose(weights, top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    _, raw = route_softmax_topk(logits, 2, norm_topk_prob=False)
    np.testing.assert_allclose(raw, top, rtol=1e-6)


@pytest.mark.parametrize("skew", [0.0, 4.0, 40.0],
                         ids=["even", "skewed", "one-expert"])
def test_routed_layer_drops_no_token_at_any_skew(skew):
    cfg = LlamaConfig.from_hf_config(HF, **HF["runtime"])
    moe = RoutedMoE(cfg.n_experts, cfg.moe_hidden_dim, cfg.moe_top_k,
                    norm_topk_prob=True, dtype=jnp.float32,
                    router="softmax_topk")
    x = jax.random.normal(jax.random.key(5), (2, 40, cfg.dim), jnp.float32)
    x = x.at[..., 0].set(1.0)  # a constant feature the router can lean on
    params = moe.init(jax.random.key(6), x)["params"]
    assert "e_score_correction_bias" not in params
    # every token pushed towards expert 3
    params["router"] = params["router"].at[0, 3].add(skew)
    got, sown = moe.apply({"params": params}, x, mutable=["intermediates"])
    hf = {"num_experts_per_tok": cfg.moe_top_k, "norm_topk_prob": True}
    flat = x.reshape(-1, cfg.dim)
    chosen, combine, _ = ref.route(params, flat, hf)
    want = ref.routed_experts(params, flat, combine)
    np.testing.assert_allclose(got.reshape(-1, cfg.dim), want, atol=F32_TOL)
    load = np.asarray(sown["intermediates"]["expert_load"][0])
    assert load.sum() == flat.shape[0] * cfg.moe_top_k  # nothing dropped
    if skew == 40.0:
        assert load[3] == flat.shape[0]


def test_block_causal_forward_against_the_reference():
    cfg = dataclasses.replace(
        LlamaConfig.from_hf_config(HF, **HF["runtime"]),
        dtype="float32", param_dtype="float32")
    model = LlamaModel(cfg)
    n_tok, lens = 32, np.array([32, 19])
    ids = jax.random.randint(jax.random.key(7), (2, n_tok), 16, 4000)
    positions = jnp.arange(n_tok)[None].repeat(2, 0)
    mask = block_causal_mask(n_tok, n_tok, 4) & padding_mask(
        jnp.asarray(lens), n_tok)
    params = model.init(jax.random.key(8), ids, positions, mask)["params"]
    logits, _ = model.apply({"params": params}, ids, positions, mask)
    for row, n in enumerate(lens):
        whole = n // 4 * 4  # a query sees keys to the end of its own block
        out = ref.forward(params, HF, ids[row, :whole], np.arange(whole),
                          ref.block_causal(whole, 4), np.arange(whole))
        np.testing.assert_allclose(logits[row, :whole], out["logits"],
                                   atol=5 * F32_TOL)


@pytest.mark.parametrize("width,lengths", [
    (256, (256, 100, 8, 0)), (512, (512, 260, 4)), (768, (700, 256))])
def test_flash_kernel_block_causal_rule_against_the_masked_form(
        width, lengths):
    assert block_causal_tile(width) in (256, 512)
    rows, heads, kv_heads, d = len(lengths), 4, 2, 32
    keys = jax.random.split(jax.random.key(width), 3)
    q = jax.random.normal(keys[0], (rows, width, heads, d), jnp.float32)
    k = jax.random.normal(keys[1], (rows, width, kv_heads, d), jnp.float32)
    v = jax.random.normal(keys[2], (rows, width, kv_heads, d), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    cache = KVCache.zeros(rows, width + 16, kv_heads, d, jnp.float32)
    views = [BlockCausalPrefill(cache, lens, 4, kernel).update(k, v)
             for kernel in (True, False)]
    flash, dense = (view.attend(q) for view in views)
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(flash[row, :n], dense[row, :n],
                                   atol=F32_TOL)
        # the view's cache holds the new keys at its head, ``n`` filled
        np.testing.assert_array_equal(views[0].cache.keys[row, :width],
                                      k[row])
        assert int(views[0].cache.length[row]) == n
    with pytest.raises(ValueError, match="block_causal"):
        flash_attention(q, k, v, lengths=lens, causal=True, block_causal=3,
                        block_q=256, block_kv=256)


def test_a_denoising_pass_writes_nothing_and_a_commit_at_each_rows_offset():
    rows, room, kv_heads, d, n = 3, 24, 2, 8, 4
    filled = jnp.asarray([8, 0, 16], jnp.int32)
    base = jax.random.normal(jax.random.key(0), (rows, room, kv_heads, d))
    cache = KVCache(base, base + 1.0, filled)
    k_new = jax.random.normal(jax.random.key(1), (rows, n, kv_heads, d))
    q = jax.random.normal(jax.random.key(2), (rows, n, 4, d))
    read = BlockPass(cache, commit=False).update(k_new, k_new * 2)
    np.testing.assert_array_equal(read.cache.keys, base)
    np.testing.assert_array_equal(read.cache.length, filled)
    kept = BlockPass(cache, commit=True).update(k_new, k_new * 2)
    np.testing.assert_array_equal(kept.cache.length, filled + n)
    for row, at in enumerate(np.asarray(filled)):
        np.testing.assert_array_equal(kept.cache.keys[row, at:at + n],
                                      k_new[row])
        np.testing.assert_array_equal(kept.cache.values[row, at:at + n],
                                      2 * k_new[row])
        np.testing.assert_array_equal(kept.cache.keys[row, :at],
                                      base[row, :at])
    # both attend to the cached keys under the row's length and to the
    # block's own, all of them: the plain softmax over that set
    np.testing.assert_allclose(read.attend(q), kept.attend(q), atol=1e-6)
    row = 0
    ks = jnp.concatenate([base[row, :8], k_new[row]])
    vs = jnp.concatenate([base[row, :8] + 1.0, 2 * k_new[row]])
    scores = jnp.einsum("qhd,khd->hqk", q[row], jnp.repeat(ks, 2, 1)) / d ** 0.5
    want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1),
                      jnp.repeat(vs, 2, 1))
    np.testing.assert_allclose(read.attend(q)[row], want, atol=1e-5)


# ------------------------------------------------------------ the sampler

def _logits(best, confidence, vocab=16):
    """``[B, n, V]`` logits whose argmax is ``best`` with softmax
    probability ``confidence`` (the rest spread evenly)."""
    best, confidence = np.asarray(best), np.asarray(confidence, np.float64)
    rest = (1.0 - confidence) / (vocab - 1)
    probs = np.broadcast_to(rest[..., None], best.shape + (vocab,)).copy()
    np.put_along_axis(probs, best[..., None], confidence[..., None], -1)
    return jnp.asarray(np.log(probs), jnp.float32)


def test_sampler_unmasks_one_a_pass_under_the_threshold():
    tokens = jnp.full((1, 4), 15)
    masked = jnp.ones((1, 4), bool)
    logits = _logits([[3, 4, 5, 6]], [[0.2, 0.5, 0.4, 0.3]])
    tokens, took, logp = unmask(logits, tokens, masked, 0.9, 1)
    assert took.tolist() == [[False, True, False, False]]
    assert tokens.tolist() == [[15, 4, 15, 15]]
    np.testing.assert_allclose(np.exp(logp[0]), [0.2, 0.5, 0.4, 0.3],
                               rtol=1e-5)
    # ties go to the lower position; ``at_least`` 2 takes the two best
    even = _logits([[3, 4, 5, 6]], [[0.5, 0.5, 0.5, 0.5]])
    _, took, _ = unmask(even, jnp.full((1, 4), 15), masked, 0.9, 1)
    assert took.tolist() == [[True, False, False, False]]
    _, took, _ = unmask(logits, jnp.full((1, 4), 15), masked, 0.9, 2)
    assert took.tolist() == [[False, True, True, False]]


def test_sampler_unmasks_every_position_over_the_threshold():
    logits = _logits([[3, 4, 5, 6]], [[0.95, 0.5, 0.99, 0.91]])
    tokens, took, _ = unmask(logits, jnp.full((1, 4), 15),
                             jnp.ones((1, 4), bool), 0.9, 1)
    assert took.tolist() == [[True, False, True, True]]
    assert tokens.tolist() == [[3, 15, 5, 6]]


def test_sampler_never_changes_a_token_once_unmasked():
    # position 0 is clean (from the prompt), 2 was unmasked earlier: their
    # logits now favour other tokens with full confidence and change nothing
    tokens = jnp.asarray([[7, 15, 9, 15]])
    masked = jnp.asarray([[False, True, False, True]])
    logits = _logits([[1, 4, 2, 6]], [[0.99, 0.3, 0.99, 0.2]])
    tokens, took, _ = unmask(logits, tokens, masked, 0.9, 1)
    assert took.tolist() == [[False, True, False, False]]
    assert tokens.tolist() == [[7, 4, 9, 15]]
    # a row with no mask left takes nothing
    tokens, took, _ = unmask(logits, tokens, jnp.zeros((1, 4), bool), 0.9, 1)
    assert not took.any() and tokens.tolist() == [[7, 4, 9, 15]]


def test_rows_whose_prompts_end_mid_block_open_their_first_block_clean(steps):
    backend, ids, lens, out, _ = steps[0]
    tokens, fresh = np.asarray(out["tokens"]), np.asarray(out["fresh"])
    pass_of = np.asarray(out["unmask_pass"])
    assert sorted(set((lens % 4).tolist())) == [0, 1, 2, 3]
    for row, n in enumerate(lens):
        whole, rest = n // 4 * 4, n % 4
        # block 0 starts with the prompt's last tokens, unchanged and clean
        np.testing.assert_array_equal(tokens[0, row, :rest],
                                      ids[row, whole:n])
        assert not fresh[0, row, :rest].any() and fresh[0, row, rest:].all()
        assert (pass_of[0, row, :rest] == -1).all()
        assert fresh[1:, row].all()
        assert len(backend.generated(out)[row]) == 16 - rest
    # random weights: one position a pass, so a whole block takes four
    # passes and every generated token has its own
    assert np.asarray(out["denoise_passes"]).tolist() == [4, 4, 4, 4]
    for row in range(len(lens)):
        for g in range(4):
            took = np.sort(pass_of[g, row][fresh[g, row]])
            np.testing.assert_array_equal(took, np.arange(len(took)))
    assert backend.config.mask_token_id not in tokens
    masked = int(np.asarray(out["positions_masked"]).sum())
    rests = lens % 4
    first = sum(sum(range(1, 5 - r)) for r in rests)  # 4-r, 4-r-1, .. 1
    assert masked == first + 3 * len(lens) * 10


# --------------------------------------------- the passes through the cache

@pytest.mark.parametrize("seed", SEEDS)
def test_every_pass_through_the_cache_agrees_with_the_full_forward(
        steps, seed):
    backend, ids, lens, out, stats = steps[seed]
    system = ref.system_rows(out, stats, np.arange(len(lens)))
    judged = ref.judge(backend.params, HF, SAMPLER, ids, lens, system,
                       tolerance=ref.TEST_TOLERANCE)
    assert judged["ok"], judged
    assert judged["tokens_compared"] == int(np.asarray(out["fresh"]).sum())
    assert judged["choices_compared"] > 3000
    assert (judged["wrong_choices"], judged["wrong_tokens"],
            judged["wrong_positions"]) == (0, 0, 0)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_in_int8_fails_the_tolerance(steps, seed):
    backend, ids, lens, out, stats = steps[seed]
    system = ref.system_rows(out, stats, np.arange(len(lens)))
    judged = ref.judge(backend.params, HF, SAMPLER, ids, lens, system,
                       variant="int8", tolerance=ref.TEST_TOLERANCE)
    assert not judged["ok"]
    assert {"prefill_kv_median", "commit_kv_median"} <= set(judged["failed"])


def test_a_pass_of_the_shared_forward_is_that_pass_alone(steps):
    """The reference's one forward a row against the form it stands for:
    one full forward of ``[prompt's whole blocks, committed blocks, the
    block]`` a pass."""
    backend, ids, lens, out, stats = steps[1]
    row = 2
    system = ref.system_rows(out, stats, np.asarray([row]))
    one = {name: np.asarray(system[name])[:, 0]
           for name in ("tokens", "fresh", "unmask_pass")}
    width = ids.shape[1]
    layout = ref.row_layout(ids[row], int(lens[row]), one, SAMPLER, width)
    params = _f32(backend.params)
    passes = [(0, 1), (2, 0), (3, 3)]
    read_at = np.concatenate([
        ref.copy_slot(width, g, c, SAMPLER) + np.arange(4) for g, c in passes])
    shared = ref.forward(params, HF, layout["ids"], layout["positions"],
                         layout["seen"], read_at)["logits"].reshape(3, 4, -1)
    whole = layout["whole"]
    for index, (g, c) in enumerate(passes):
        block = np.where(one["fresh"][g] & (one["unmask_pass"][g] >= c),
                         SAMPLER["mask_token_id"], one["tokens"][g])
        sequence = np.concatenate(
            [ids[row, :whole], one["tokens"][:g].reshape(-1), block])
        n_tok = len(sequence)
        alone = ref.forward(params, HF, sequence, np.arange(n_tok),
                            ref.block_causal(n_tok, 4),
                            np.arange(n_tok - 4, n_tok))["logits"]
        np.testing.assert_allclose(shared[index], alone, atol=5 * F32_TOL)


def test_compact_prefill_is_bit_equal_on_the_real_positions(steps):
    backend, ids, lens, _, _ = steps[0]
    whole = lens // 4 * 4
    rows, width = ids.shape
    args = (backend.params, jnp.asarray(ids), jnp.asarray(lens))
    full, full_stats = backend._prefill(*args, gen_blocks=4,
                                        prefill_capacity=None)
    from music_analyst_tpu.models.compact_stream import compact_capacity

    capacity = compact_capacity(int(whole.sum()), rows, width)
    assert capacity < rows * width
    compact, stats = backend._prefill(*args, gen_blocks=4,
                                      prefill_capacity=capacity)
    for a, b in zip(full, compact):
        for row, n in enumerate(whole):
            np.testing.assert_array_equal(a.keys[row, :n], b.keys[row, :n])
            np.testing.assert_array_equal(a.values[row, :n],
                                          b.values[row, :n])
        np.testing.assert_array_equal(a.length, whole)
    for row, n in enumerate(whole):
        np.testing.assert_array_equal(full_stats["chosen"][:, row, :n],
                                      stats["chosen"][:, row, :n])
    # the load counts the real positions' assignments alone
    assert float(stats["expert_load_mean"][0]) * 8 == whole.sum() * 2


# a row shorter than a block (nothing of it is prefilled), one that fills
# the width, a block + 1 (648 whole-block positions in 768 slots); rows that
# fill their rung to the last slot (1,024 of 1,024, no filler)
_WIDE_STEPS = {"ragged": [3, 512, 101, 38],
               "fills-the-capacity": [512, 256, 2, 259]}


def _wide_step(clf, lengths, width=512):
    ids = np.random.default_rng(5).integers(
        16, clf.config.offline_vocab_size, (len(lengths), width))
    return ids.astype(np.int32), np.asarray(lengths, np.int32)


@pytest.fixture(scope="module")
def clf32(clf):
    """``clf``'s model and weights computing in float32: the packed kernel
    rounds its probabilities to the values' dtype before P.V, the ``[B,
    S]`` kernel does not, so only in float32 do the two run the same
    arithmetic (summed in other orders)."""
    return BlockDiffusionClassifier(
        dataclasses.replace(clf.config, dtype="float32"), seed=SEEDS[0])


@pytest.mark.parametrize("lengths", list(_WIDE_STEPS.values()),
                         ids=list(_WIDE_STEPS))
def test_compact_stream_equals_the_padded_prefill_on_every_whole_block(
        clf32, lengths, monkeypatch):
    """At a width and a rung ``llama.runs_compact`` admits, a prefill that
    declares a ``prefill_capacity`` keeps its hidden state on the compact
    token set through every block (projections, QK-norm, RoPE, norms,
    residual adds and experts on ``capacity`` slots; the packed kernel
    reads queries, keys and values on the stream and writes its result
    there, keys and values are put back at ``[B, S]`` for the cache).
    Against the padded prefill (``runs_compact`` answering no, the
    capacity withheld: every position through every layer, the ``[B, S]``
    kernel of the widths the packed one refuses), both in float32
    (``clf32``): the caches on ``[0, whole[row])`` (the first layer's to
    the bit, the others within one bfloat16 step of the stored value: the
    two kernels sum their tiles in other orders), zeros behind it, the
    chosen experts, and the tokens the block loop generates from either."""
    from music_analyst_tpu.models import compact_stream, llama
    from music_analyst_tpu.models.compact_stream import compact_capacity

    clf = clf32
    ids, lens = _wide_step(clf, lengths)
    rows, width = ids.shape
    whole = lens // 4 * 4
    assert 0 in whole and width in whole
    capacity = compact_capacity(int(whole.sum()), rows, width)
    assert capacity == {648: 768, 1024: 1024}[int(whole.sum())]
    assert llama.runs_compact(clf.config, ids.shape, capacity)
    args = (clf.params, jnp.asarray(ids), jnp.asarray(lens))
    with monkeypatch.context() as patched:
        patched.setattr(compact_stream, "runs_compact", lambda *a: False)
        full, full_stats = clf._prefill(*args, gen_blocks=4)
    compact, stats = clf._prefill(*args, gen_blocks=4,
                                  prefill_capacity=capacity)
    # both are the kernel's; the one that declared a capacity ran compact
    at_shape = [(key, record) for key, record in clf._prefill.records.items()
                if f"int32[{rows}, {width}]" in key]
    assert len(at_shape) >= 2
    for key, record in at_shape:
        took = 3 if "prefill_capacity" in key else 0
        path = "block_causal_packed" if took else "block_causal"
        assert record.attention_paths == {path: 3}
        assert record.traced_paths.get("gqa.compact", 0) == took
        assert record.traced_paths.get("moe.compact", 0) == took
    real = np.arange(width)[None, :] < whole[:, None]
    for layer, (a, b) in enumerate(zip(full, compact)):
        np.testing.assert_array_equal(b.length, whole)
        for name in ("keys", "values"):
            want = np.asarray(getattr(a, name), np.float32)[:, :width]
            got = np.asarray(getattr(b, name), np.float32)[:, :width]
            if layer == 0:
                # a slot's projections are its position's
                np.testing.assert_array_equal(got[real], want[real])
            else:
                # a bfloat16 cache entry 1 <= |x| < 2 steps by 2**-7
                np.testing.assert_allclose(got[real], want[real],
                                           rtol=2 ** -7, atol=2 ** -7)
            assert not got[~real].any()
    np.testing.assert_array_equal(
        np.asarray(stats["chosen"])[:, real],
        np.asarray(full_stats["chosen"])[:, real])
    assert not np.asarray(stats["chosen"])[:, ~real].any()
    # the load counts the whole blocks' assignments alone
    assert float(stats["expert_load_mean"][0]) * 8 == whole.sum() * 2
    want, _ = clf._denoise(*args[:1], full, *args[1:], gen_blocks=4)
    got, _ = clf._denoise(*args[:1], compact, *args[1:], gen_blocks=4)
    for name in ("tokens", "fresh", "unmask_pass"):
        np.testing.assert_array_equal(got[name], want[name])


@pytest.mark.parametrize("words,compact", [((300, 30, 120, 9), True),
                                           ((30, 55, 70, 18), False)],
                         ids=["compact", "padded"])
def test_tokens_computed_counts_the_slots_the_prefill_ran(clf, words,
                                                          compact):
    """``decoder.tokens_computed`` is what went through the layers: the
    ``capacity`` slots of a prefill that ran on the compact stream, ``rows
    * width`` of one that did not, and the passes' positions either way;
    ``decoder.tokens_real`` is the work, whatever implements it."""
    from music_analyst_tpu.models import llama
    from music_analyst_tpu.telemetry import get_telemetry

    rng = np.random.default_rng(11)
    texts = [" ".join(rng.choice(_WORDS, size=n)) for n in words]
    transferred = clf.transfer(clf.prepare(texts))
    rows, width = transferred[1].shape
    prefilled, capacity = transferred[3][1], transferred[3][3]
    assert capacity < rows * width
    assert llama.runs_compact(clf.config, (rows, width), capacity) == compact
    tel = get_telemetry()
    names = ("decoder.tokens_computed", "decoder.tokens_real",
             "diffusion.denoise_passes", "diffusion.commit_passes")
    before = [tel.counters.get(name, 0) for name in names]
    clf.collect(clf.launch(transferred))
    computed, real, denoise, commit = (
        tel.counters.get(name, 0) - b for name, b in zip(names, before))
    pass_positions = rows * 4 * (denoise + commit)
    assert real == prefilled + pass_positions
    assert computed == (capacity if compact else rows * width) + pass_positions


# ``sha256(lowered.as_text())[:16]`` (a compile record's ``hlo_fingerprint``)
# of the scoring program at 4 x 512 with lengths 300, 41, 256, 101, with no
# capacity declared and at 768 slots.  The 768-slot programs are as the
# tree before the block-diffusion prefill took the stream lowered them
# (Granite's as its attention layer's packed flash kernel lowers it); the
# grouped-query decoder's two are one program, as the masked attention
# that reads each key/value head in place lowers it.  A prefill that declares its lengths and
# no capacity runs the compact stream on rows x width slots
# (``models/compact_stream.prefill_stream``): the first hash of the three
# compact presets is their 2,048-slot program, which no longer holds the
# padded latent kernel.  Ling's and Granite's hand back, beside the caches of
# ``probe_rows``, those rows' logits at the prompt's last position.  A PR
# that means to change one of these programs replaces its pair here.
_KEPT_PROGRAMS = {
    "kanana-tiny": ("f0d9b5b7793d6050", "0e77949cc3e2799c"),
    "ling-tiny": ("11395e945d9bb268", "015f89b59e65a8cf"),
    "granite-tiny": ("acfc95cf1ae3cd34", "450da477895db205"),
    "llama3-tiny": ("665d91b43ea83077", "665d91b43ea83077"),
}


@pytest.mark.parametrize("preset", list(_KEPT_PROGRAMS))
def test_the_other_decoders_answer_and_lower_as_they_did(preset):
    """Who takes the compact stream beside the block-diffusion prefill, and
    that their programs are text for text what they were: the latent, the
    KDA and the state-space configurations run compact where they did, an
    autoregressive grouped-query decoder never does (a declared capacity
    changes nothing of its program)."""
    import hashlib

    from music_analyst_tpu.engines.sentiment import get_backend
    from music_analyst_tpu.models import llama

    clf = get_backend(preset, seed=0)
    cfg = clf.config
    takes = preset != "llama3-tiny"
    assert cfg.compact_stream == takes and not cfg.block_diffusion
    assert llama.runs_compact(cfg, (4, 512), 768) == takes
    assert llama.runs_compact(cfg, (32, 1024), 12288) == takes
    assert not llama.runs_compact(cfg, (4, 512), None)       # a mesh
    assert llama.runs_compact(cfg, (4, 512), 4 * 512) == takes   # full rows
    assert not llama.runs_compact(cfg, (4, 64), 128)         # narrow
    extra = {}
    if cfg.recurrent_state:
        extra["probe_rows"] = jnp.asarray(np.minimum(clf.probe_rows, 3))
    args = (clf.params, jnp.zeros((4, 512), jnp.int32),
            jnp.asarray([300, 41, 256, 101], jnp.int32),
            jnp.asarray(clf._label_ids), jnp.asarray(clf._label_lens))
    lowered = tuple(
        hashlib.sha256(clf._score_labels.lower(
            *args, prefill_capacity=capacity, **extra).as_text().encode()
        ).hexdigest()[:16] for capacity in (None, 768))
    assert lowered == _KEPT_PROGRAMS[preset]


def test_a_partitioned_prefill_keeps_the_padded_program(clf):
    """Under a mesh of more than one device the prefill withholds the
    lengths (the kernel's call is opaque to the partitioner) and is handed
    no capacity: the padded program, whatever capacity a caller names."""
    from music_analyst_tpu.models import llama
    from music_analyst_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec((("dp", 1), ("ep", 2), ("tp", 1))),
                      devices=jax.devices()[:2])
    meshed = BlockDiffusionClassifier(
        config=clf.config, mesh=mesh, max_prompt_len=clf.max_prompt_len)
    ids, lens = _wide_step(clf, _WIDE_STEPS["ragged"])
    assert llama.runs_compact(clf.config, ids.shape, 768)
    transferred = meshed.transfer(("", ids, lens))
    assert transferred[3][3] is None  # no capacity where no length is read
    args = (meshed.params, jnp.asarray(ids), jnp.asarray(lens))

    def text(program, **static):
        return program.lower(*args, gen_blocks=4, **static).as_text()

    assert text(meshed._prefill, prefill_capacity=768) == text(
        meshed._prefill)
    assert text(clf._prefill, prefill_capacity=768) != text(clf._prefill)


# ----------------------------------------------------- the normal path

def test_labels_come_from_the_generated_tokens(clf):
    positive = next(i for i, l in clf._label_of.items() if l == "Positive")
    negative = next(i for i, l in clf._label_of.items() if l == "Negative")
    assert clf._label([40, 41, negative, positive]) == "Negative"
    assert clf._label([40, 41, 42]) == "Neutral"
    assert clf._label([]) == "Neutral"
    labels = clf.classify_batch(["sunny day " * 5, "", "rain " * 30])
    assert labels[1] == "Neutral" and len(labels) == 3
    assert set(labels) <= {"Positive", "Neutral", "Negative"}
    assert clf.classify_batch_by_generation(["la la"]) == clf.classify_batch(
        ["la la"])
    with pytest.raises(ValueError, match="blocks"):
        clf.generate_batch(["la la"], max_new_tokens=64)
    assert isinstance(clf.generate("la la la"), str)


def test_runtimes_that_assume_one_token_a_step_refuse_the_model(clf):
    from music_analyst_tpu.serving.decode_runtime import (
        decode_runtime_refusal,
    )

    assert clf.decode_runtime_refusal == BLOCK_STEP_REFUSAL
    reason = decode_runtime_refusal(clf, "paged")
    assert "diffusion over blocks" in reason and "paged" in reason


def test_cli_writes_the_jobs_files_and_counts_what_a_step_did(tmp_path):
    from music_analyst_tpu.cli.main import main

    out = tmp_path / "out"
    assert main(["sentiment",
                 os.path.join(REPO, "tests", "fixtures", "mini_songs.csv"),
                 "--model", "sdar-tiny", "--batch-size", "4",
                 "--output-dir", str(out)]) == 0
    totals = json.loads((out / "sentiment_totals.json").read_text())
    assert sum(totals.values()) == 8
    assert (out / "sentiment_details.csv").exists()
    manifest = json.loads((out / "run_manifest.json").read_text())
    counters = manifest["counters"]
    assert counters["diffusion.blocks"] == 2 * 4  # two steps of four rows
    assert counters["diffusion.commit_passes"] == 8
    assert counters["diffusion.denoise_passes"] == 32
    assert 8 * 13 <= counters["diffusion.tokens_unmasked"] <= 8 * 16
    assert counters["decoder.tokens_real"] < counters["decoder.tokens_computed"]
    assert counters["traced.moe.softmax_topk"] >= 3
    assert counters["traced.moe.compact"] >= 3
    assert manifest["gauges"]["kv_cache_bytes"] > 0
    compiled = {r["name"]: r for r in manifest["profiling"]["compiles"]}
    assert {"llama_diffusion_prefill", "llama_diffusion_denoise"} <= set(
        compiled)
    assert "block_over_cache" in compiled[
        "llama_diffusion_denoise"]["attention_paths"]
    spans = [json.loads(line) for line in
             (out / "telemetry.jsonl").read_text().splitlines()]
    step = next(s["attrs"] for s in spans
                if s.get("name") == "compute" and "attrs" in s)
    assert {"rows", "width", "tokens_real", "tokens_prefilled", "token_pairs",
            "pass_pairs", "moe_capacity", "gen_blocks", "denoise_passes",
            "commit_passes", "positions_masked", "tokens_unmasked",
            "expert_load_max_over_mean"} <= set(step)
    assert (step["gen_blocks"], step["commit_passes"],
            step["denoise_passes"]) == (4, 4, 16)
