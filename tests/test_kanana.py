"""The ``kanana`` decoder kinds (latent attention with a latent cache,
sigmoid-routed experts with no dropped token, shared experts) against the
plain float32 reference ``perfbench/reference/deepseek_v3_f32.py``, at the
``kanana-tiny`` size with seeded weights.

Layer tests run the program's modules in float32 on the reference's own
inputs, so they hold the equations (tolerance: float32 rounding).  The
end-to-end tests run the system as it is served, bfloat16, and hold it to
``TEST_TOLERANCE``, which is set from the measured bfloat16 differences and
is tight enough that int8 experts or a dropped shared expert fail it.
"""

from __future__ import annotations

import dataclasses
import functools
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

from reference import deepseek_v3_f32 as ref  # noqa: E402

from music_analyst_tpu.models import llama  # noqa: E402
from music_analyst_tpu.models.layers import causal_mask  # noqa: E402
from music_analyst_tpu.models.llama import (  # noqa: E402
    PRESETS,
    LlamaConfig,
    LlamaZeroShotClassifier,
    init_caches,
)
from music_analyst_tpu.models.mla import LatentCache, MLAttention  # noqa: E402
from music_analyst_tpu.models.compact_stream import (  # noqa: E402
    compact_capacity,
)
from music_analyst_tpu.models.moe import (  # noqa: E402
    SigmoidRoutedMoE,
    route_sigmoid_noaux,
)

F32_TOL = 2e-4  # float32 program against float32 reference


def _preset(name):
    path = os.path.join(REPO, "music_analyst_tpu", "models", "presets",
                        name + ".json")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


HF = _preset("kanana-tiny")

_WORDS = ("love rain night baby tears dance road fire cold heart sun blue "
          "you me the and never always gone stay").split()


def _lyrics(seed: int, rows: int):
    rng = np.random.default_rng(seed)
    return [" ".join(rng.choice(_WORDS, size=int(n)))
            for n in rng.integers(5, 400, size=rows)]


# 12 seeded lyrics of 5 to 400 words (several past 128 tokens, a 512-wide
# step: the prefill is the expanded form, through the kernel), and the
# empty lyric.  Seed 1 because a router tie that falls the other way in
# the served program matters there whichever expanded form runs: handed the
# program's choices the reference's scores move by up to 0.82 (the kernel)
# or 0.49 (``blocked_attention``), thirty times the 0.024 / 0.027 between
# program and reference.  Which ties flip is rounding's to decide, and at
# seed 0 they moved a score by 0.022 (kernel) and 0.140 (blocked), on
# either side of that noise (PERF.md section 6, PR 28).
LYRICS = _lyrics(1, 12) + [""]


@functools.lru_cache(maxsize=None)
def _backend(name: str):
    from music_analyst_tpu.engines.sentiment import get_backend

    return get_backend(name)


@pytest.fixture(scope="module")
def clf():
    return _backend("kanana-tiny")


@pytest.fixture(scope="module")
def clf32(clf):
    """``clf``'s model computing in float32 (its programs take ``clf``'s
    weights): the prefill kernel rounds its probabilities to the values'
    dtype before P.V and divides after, the blocked form divides first,
    and the padded layer reads its keys back from the cache where the
    stream's reads them as computed, so only in float32, caches too, do
    the two run the same arithmetic (summed in other orders)."""
    return LlamaZeroShotClassifier(
        config=dataclasses.replace(clf.config, dtype="float32"), seed=0)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _mla(cfg: LlamaConfig, **kw):
    return MLAttention(
        n_heads=cfg.n_heads, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, rope_theta=cfg.rope_theta,
        rope_interleave=cfg.rope_interleave, max_positions=cfg.max_seq_len,
        norm_eps=cfg.rms_norm_eps, dtype=jnp.float32, **kw)


def _moe(cfg: LlamaConfig, dtype=jnp.float32):
    return SigmoidRoutedMoE(
        cfg.n_experts, cfg.moe_hidden_dim, cfg.moe_top_k,
        n_shared=cfg.n_shared_experts,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob, dtype=dtype)


def _hidden(rows, n_tok, dim, seed=0):
    return jax.random.normal(jax.random.key(seed), (rows, n_tok, dim),
                             jnp.float32)


# ----------------------------------------------------------- configuration

def test_presets_are_built_from_their_files(clf):
    cfg = clf.config
    assert (cfg.attention, cfg.moe_router) == ("mla", "sigmoid_noaux")
    assert (cfg.n_layers, cfg.first_k_dense_replace) == (3, 1)
    assert [cfg.routed_layer(i) for i in range(3)] == [False, True, True]
    assert (cfg.n_experts, cfg.moe_top_k, cfg.n_shared_experts) == (8, 2, 1)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (16, 16, 8, 16)
    big = PRESETS["kanana-2-30b-a3b"]()
    published = _preset("kanana-2-30b-a3b")
    assert (big.dim, big.n_heads, big.hidden_dim) == (2048, 32, 6144)
    assert (big.n_experts, big.moe_hidden_dim, big.moe_top_k,
            big.n_shared_experts) == (128, 768, 6, 2)
    assert (big.kv_lora_rank, big.qk_nope_head_dim, big.qk_rope_head_dim,
            big.v_head_dim, big.vocab_size) == (512, 128, 64, 128, 128256)
    assert big.n_layers == published["num_hidden_layers"] == 7
    assert big.param_dtype == big.dtype == "bfloat16"
    # one compiled width at the published size; the test size trims
    assert (big.prompt_width_floor, cfg.prompt_width_floor) == (1024, 64)
    short, _ = clf._encode_prompts(["la la"])
    assert short.shape[1] == 64


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("moe_layer_freq", 2), ("scoring_func", "softmax"),
    ("topk_method", "greedy"), ("rope_scaling", {"type": "yarn"}),
    ("model_type", "llama"),
])
def test_unsupported_configuration_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        LlamaConfig.from_hf_config({**HF, key: value})


def test_init_is_bfloat16_on_device_with_a_live_correction_bias(clf):
    leaves = jax.tree_util.tree_leaves_with_path(clf.params)
    for path, leaf in leaves:
        name = jax.tree_util.keystr(path)
        if any(k in name for k in ("scale", "router", "correction_bias")):
            assert leaf.dtype == jnp.float32, name
        else:
            assert leaf.dtype == jnp.bfloat16, name
    moe = clf.params["layer_1"]["feed_forward_moe"]
    bias = np.asarray(moe["e_score_correction_bias"])
    assert 0 < np.abs(bias).max() < 0.1
    # the two routed layers are the same program run on different keys
    other = clf.params["layer_2"]["feed_forward_moe"]
    assert not np.array_equal(np.asarray(moe["gate_experts"], np.float32),
                              np.asarray(other["gate_experts"], np.float32))
    std = float(np.asarray(moe["gate_experts"], np.float32).std())
    assert abs(std - clf.config.dim ** -0.5) < 0.02


def test_hash_word_tokenizer_covers_the_vocabulary():
    from music_analyst_tpu.models.tokenization import HashWordLMTokenizer

    tok = HashWordLMTokenizer(128_256)
    text = "Hold me tight, don't let go! " * 40
    ids, n = tok.encode(text, 1024)
    words = len(text.split())
    assert ids[0] == tok.bos_id and n - 1 <= 2 * words  # ~1 token a word/mark
    assert ids[:n].max() < 128_256 and ids[1:n].min() >= 16
    assert len(set(ids[1:n].tolist())) >= 7
    again, _ = tok.encode(text, 1024)
    assert np.array_equal(ids, again)
    short, m = tok.encode(text, 32)
    assert m == 32 and np.array_equal(short, ids[:32])
    assert tok.decode([5, 17, tok.eos_id]) == "<5> <17>"


# ------------------------------------------------------------ layer kinds

def test_mla_expanded_matches_reference(clf):
    cfg = clf.config
    p = _f32(clf.params["layer_1"]["attention"])
    h = _hidden(2, 256, cfg.dim)
    positions = jnp.broadcast_to(jnp.arange(256), (2, 256))
    want = ref.mla_attention(p, h, positions, HF)
    # 256 queries in blocks of 128: the blocked form
    got = _mla(cfg).apply({"params": p}, h, causal_mask(256, 256, 0),
                          positions)
    assert float(jnp.abs(got - want).max()) < F32_TOL
    whole = _mla(cfg, block_q=512).apply(
        {"params": p}, h, causal_mask(256, 256, 0), positions)
    assert float(jnp.abs(got - whole).max()) < F32_TOL


def test_mla_absorbed_through_the_cache_equals_expanded(clf):
    cfg = clf.config
    p = _f32(clf.params["layer_2"]["attention"])
    rows, n_prompt, n_new = 2, 24, 5
    total = n_prompt + n_new
    h = _hidden(rows, total, cfg.dim, seed=3)
    positions = jnp.broadcast_to(jnp.arange(total), (rows, total))
    want = ref.mla_attention(p, h, positions, HF)

    def run(absorb_max_queries):
        mla = _mla(cfg, absorb_max_queries=absorb_max_queries)
        cache = LatentCache.zeros(rows, total, cfg.kv_lora_rank,
                                  cfg.qk_rope_head_dim, jnp.float32)
        out, cache = mla.apply(
            {"params": p}, h[:, :n_prompt],
            causal_mask(n_prompt, total, 0), positions[:, :n_prompt], cache)
        outs = [out]
        for t in range(n_prompt, total):  # one token at a time
            mask = (jnp.arange(total) <= t)[None, None, None, :]
            out, cache = mla.apply({"params": p}, h[:, t:t + 1], mask,
                                   positions[:, t:t + 1], cache)
            outs.append(out)
        assert int(cache.length) == total
        return jnp.concatenate(outs, axis=1)

    absorbed, expanded = run(128), run(0)
    assert float(jnp.abs(absorbed - want).max()) < F32_TOL
    assert float(jnp.abs(expanded - want).max()) < F32_TOL
    # the cache holds kv_lora_rank + rope values a token
    cache = init_caches(cfg, 2, 10)[0]
    assert isinstance(cache, LatentCache)
    assert cache.latents.nbytes + cache.rope_keys.nbytes == 2 * 10 * 24 * 2


def test_router_choice_and_weights_match_reference(clf):
    cfg = clf.config
    p = clf.params["layer_1"]["feed_forward_moe"]
    h = _hidden(4, 64, cfg.dim, seed=5)
    logits = h.reshape(-1, cfg.dim) @ p["router"]
    chosen, weights = route_sigmoid_noaux(
        logits, p["e_score_correction_bias"], cfg.moe_top_k,
        cfg.routed_scaling_factor)
    _, want_chosen, combine, _ = ref.route(p, h.reshape(-1, cfg.dim), HF)
    assert np.array_equal(np.sort(chosen, -1), np.sort(want_chosen, -1))
    got = np.zeros(combine.shape, np.float32)
    np.put_along_axis(got, np.asarray(chosen), np.asarray(weights), -1)
    assert np.abs(got - np.asarray(combine)).max() < 1e-5
    assert np.allclose(np.asarray(weights).sum(-1),
                       cfg.routed_scaling_factor, atol=1e-4)


def test_correction_bias_moves_the_choice_and_not_the_weight():
    # sigmoid scores: experts 0, 1 lead; the bias lifts expert 3 over 1
    logits = jnp.asarray([[2.0, 1.0, -1.0, 0.9]])
    none = jnp.zeros(4)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.05])
    plain, w_plain = route_sigmoid_noaux(logits, none, 2, 1.0)
    moved, w_moved = route_sigmoid_noaux(logits, bias, 2, 1.0)
    assert sorted(np.asarray(plain)[0].tolist()) == [0, 1]
    assert sorted(np.asarray(moved)[0].tolist()) == [0, 3]
    s = np.asarray(jax.nn.sigmoid(logits))[0]
    order = np.asarray(moved)[0].tolist()
    want = np.asarray([s[e] for e in order]) / (s[0] + s[3])
    assert np.allclose(np.asarray(w_moved)[0], want, atol=1e-6)  # no bias in
    assert not np.allclose(np.asarray(w_moved)[0].sum() * (s[0] + s[3]),
                           s[0] + s[3] + 0.05)


def test_experts_drop_nothing_under_the_most_uneven_routing(clf):
    cfg = clf.config
    p = dict(_f32(clf.params["layer_1"]["feed_forward_moe"]))
    # a bias this large sends every token to experts 6 and 2
    p["e_score_correction_bias"] = jnp.zeros(cfg.n_experts).at[
        jnp.asarray([6, 2])].set(10.0)
    h = _hidden(3, 40, cfg.dim, seed=7)
    want, chosen, _ = ref.moe_ffn(p, h, HF)
    assert set(np.asarray(chosen).reshape(-1).tolist()) == {2, 6}
    got, sown = _moe(cfg).apply({"params": p}, h, mutable=["intermediates"])
    assert float(jnp.abs(got - want).max()) < F32_TOL
    load = np.asarray(sown["intermediates"]["expert_load"][0])
    assert load.tolist() == [0, 0, 120, 0, 0, 0, 120, 0]  # all 120 tokens
    # and under the seeded routing
    q = _f32(clf.params["layer_2"]["feed_forward_moe"])
    want, _, _ = ref.moe_ffn(q, h, HF)
    got, sown = _moe(cfg).apply({"params": q}, h, mutable=["intermediates"])
    assert float(jnp.abs(got - want).max()) < F32_TOL
    assert int(sown["intermediates"]["expert_load"][0].sum()) == 120 * 2


def test_shared_experts_reach_every_token(clf):
    cfg = clf.config
    p = dict(_f32(clf.params["layer_1"]["feed_forward_moe"]))
    p["down_experts"] = jnp.zeros_like(p["down_experts"])  # routed part off
    h = _hidden(2, 16, cfg.dim, seed=9)
    got = _moe(cfg).apply({"params": p}, h)
    want = ref.swiglu(p["shared_experts"], h)
    assert float(jnp.abs(want).max()) > 0.1
    assert float(jnp.abs(got - want).max()) < F32_TOL


def test_router_ties_are_counted_not_hidden(clf):
    """bfloat16 activations against float32: a token whose k-th and
    (k+1)-th corrected scores are further apart than ``margin`` chooses the
    same experts; the others are counted, and are few."""
    cfg = clf.config
    p = clf.params["layer_1"]["feed_forward_moe"]
    h = _hidden(8, 128, cfg.dim, seed=11).reshape(-1, cfg.dim)
    scores, want, _, _ = ref.route(p, h, HF)
    corrected = np.sort(np.asarray(scores) + np.asarray(
        p["e_score_correction_bias"]), -1)
    gap = corrected[:, -cfg.moe_top_k] - corrected[:, -cfg.moe_top_k - 1]
    logits = jnp.dot(h.astype(jnp.bfloat16).astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    got, _ = route_sigmoid_noaux(logits, p["e_score_correction_bias"],
                                 cfg.moe_top_k, cfg.routed_scaling_factor)
    same = (np.sort(np.asarray(got), -1)
            == np.sort(np.asarray(want), -1)).all(-1)
    margin = 0.01
    assert same[gap > margin].all()
    close = int((gap <= margin).sum())
    assert 0 < close < 0.15 * len(gap)
    assert int((~same).sum()) <= close


# ------------------------------------------------------------- end to end

def _prompts(clf, texts):
    ids, lens = clf._encode_prompts(texts)
    return np.asarray(ids), np.asarray(lens)


def _program(clf, params, ids, lens):
    """Label scores and the experts every position ran, from the scoring
    program as it is served."""
    scores, stats = clf._score_labels(
        params, jnp.asarray(ids), jnp.asarray(lens),
        jnp.asarray(clf._label_ids), jnp.asarray(clf._label_lens))
    prefer = ref.prefer_from_system(stats["chosen"], stats["chosen_labels"],
                                    lens)
    return np.asarray(scores, np.float64), prefer


def _program_scores(clf, params, ids, lens):
    return _program(clf, params, ids, lens)[0]


def _reference(clf, ids, lens, prefer=None, **kw):
    return ref.label_scores(
        clf.params, HF, ids, lens, clf._label_ids, clf._label_lens,
        prefer=prefer, margin=ref.TEST_TOLERANCE["route_margin"], **kw)


@pytest.fixture(scope="module")
def scored(clf):
    ids, lens = _prompts(clf, LYRICS)
    assert ids.shape[1] == 512 and lens.max() > 128  # expanded, the kernel
    got, prefer = _program(clf, clf.params, ids, lens)
    return ids, lens, got, _reference(clf, ids, lens, prefer)


def test_label_scores_through_the_latent_cache_match_reference(clf, scored):
    """Router ties are the system's to break (the reference takes its
    experts where they lie within the margin and counts the rest); given
    equal choices the scores agree to bfloat16's rounding."""
    ids, lens, got, want = scored
    tol = ref.TEST_TOLERANCE
    routing = want["routing"]
    # a prompt's positions and the two of ``word + EOS``, in every label
    assert clf._label_ids.shape == (3, 2)
    assert routing["compared"] == 2 * 3 * int((lens + 2).sum())
    assert 0 < routing["differ"] < 0.05 * routing["compared"]
    assert routing["wrong"] == tol["wrong_choices"]
    assert routing["deepest_tie"] < tol["route_margin"]
    diff = np.abs(got - want["scores"])
    assert np.median(diff) < tol["label_score_median"], diff
    assert diff.max() < tol["label_score_max"], diff
    # left to its own choices the reference drifts off on the flipped rows
    free = _reference(clf, ids, lens)
    assert np.abs(got - free["scores"]).max() > diff.max()
    record = list(clf._score_labels.records.values())[-1]
    assert record.traced_paths["mla.expanded"] == 3      # the prefill
    assert record.attention_paths == {"mla_flash_packed": 3}  # one kernel
    assert record.traced_paths["mla.absorbed"] == 3      # label passes
    assert record.traced_paths["moe.grouped"] == 4       # 2 layers x 2


def test_prefill_last_position_logits_match_reference(clf, scored):
    ids, lens, _, want = scored
    rows, width = ids.shape
    caches = init_caches(clf.config, rows, width + 8)
    positions = jnp.broadcast_to(jnp.arange(width), (rows, width))
    mask = causal_mask(width, width + 8, 0)
    logits, _ = clf.model.apply(
        {"params": clf.params}, jnp.asarray(ids), positions, mask, caches,
        last_position=jnp.asarray(lens) - 1)
    diff = np.abs(np.asarray(logits[:, 0], np.float64) - want["last_logits"])
    assert np.median(diff) < ref.TEST_TOLERANCE["last_logit_median"]


def _int8_rounded(w):
    w = jnp.asarray(w, jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=1, keepdims=True) / 127.0
    return (jnp.round(w / scale) * scale).astype(jnp.bfloat16)


def test_a_dropped_shared_expert_and_int8_fail_the_tolerance(clf, scored):
    """End to end: a system that leaves the shared experts out fails the
    score limits, and the reference computed in int8 (the precision below
    the configuration's) fails by its choices and by the median."""
    ids, lens, _, want = scored
    tol = ref.TEST_TOLERANCE
    params = jax.tree_util.tree_map(lambda a: a, clf.params)
    for i in (1, 2):
        moe = dict(params[f"layer_{i}"]["feed_forward_moe"])
        shared = dict(moe["shared_experts"])
        shared["down_proj"] = {"kernel": jnp.zeros_like(
            shared["down_proj"]["kernel"])}
        moe["shared_experts"] = shared
        params[f"layer_{i}"] = {**params[f"layer_{i}"],
                                "feed_forward_moe": moe}
    got, prefer = _program(clf, params, ids, lens)
    diff = np.abs(got - _reference(clf, ids, lens, prefer)["scores"])
    assert np.median(diff) > 3 * tol["label_score_median"]
    assert diff.max() > tol["label_score_max"]
    low = _reference(clf, ids, lens, variant="int8")
    judged = _reference(clf, ids, lens, low["chosen"])
    assert judged["routing"]["wrong"] > tol["wrong_choices"]
    assert np.median(np.abs(low["scores"] - judged["scores"])) > tol[
        "label_score_median"]


def test_expert_layer_in_bfloat16_meets_a_limit_int8_experts_fail(clf):
    """The expert layer as served (bfloat16) against the reference, by the
    median over tokens of a token's largest error over the output's RMS;
    the same layer with its expert weights rounded to int8 fails."""
    cfg = clf.config
    tol = ref.TEST_TOLERANCE["expert_layer_median"]
    p = clf.params["layer_1"]["feed_forward_moe"]
    h = _hidden(8, 64, cfg.dim, seed=1).astype(jnp.bfloat16)
    want, _, _ = ref.moe_ffn(p, h.astype(jnp.float32), HF)
    rms = float(jnp.sqrt(jnp.mean(want ** 2)))

    def reading(params):
        got = _moe(cfg, dtype=jnp.bfloat16).apply({"params": params}, h)
        err = np.abs(np.asarray(got, np.float32) - np.asarray(want)) / rms
        return float(np.median(err.max(-1)))

    int8 = dict(p)
    for name in ("gate_experts", "up_experts", "down_experts"):
        int8[name] = _int8_rounded(p[name])
    assert reading(p) < tol < reading(int8)


def test_generate_batch_logits_step_by_step_match_full_forward(clf):
    """``generate_batch`` (prefill, then one token a step through the
    latent cache, one program) against the reference's full forward: the
    same steps taken one at a time, fed the tokens ``generate_batch`` chose,
    give logits that match the full forward at every step, and every chosen
    token is the largest logit up to rounding."""
    prompts = ["hold me close tonight", "rain on the window, " * 6]
    steps = 4
    texts = clf.generate_batch(prompts, max_new_tokens=steps,
                               early_exit=False)
    tokens = np.asarray([[int(t.strip("<>")) for t in text.split()]
                         for text in texts])
    assert tokens.shape == (2, steps)
    ids, lens = clf.tokenizer.encode_batch(prompts, clf.max_prompt_len)
    ids, lens = clf._trim_prompt_pad(ids, lens)
    rows, width = ids.shape
    total = width + steps
    caches = init_caches(clf.config, rows, total)
    positions = jnp.broadcast_to(jnp.arange(width), (rows, width))
    lens_j = jnp.asarray(lens)
    kv = jnp.arange(total)[None, None, None, :]
    mask = causal_mask(width, total, 0) & (kv < lens_j[:, None, None, None])
    logits, caches = clf.model.apply(
        {"params": clf.params}, jnp.asarray(ids), positions, mask, caches,
        last_position=lens_j - 1)
    caches = [c.with_length(width) for c in caches]
    step_logits = [np.asarray(logits[:, 0], np.float64)]
    for t in range(steps - 1):
        step_mask = (kv < lens_j[:, None, None, None]) | (
            (kv >= width) & (kv - width <= t))
        logits, caches = clf.model.apply(
            {"params": clf.params}, jnp.asarray(tokens[:, t])[:, None],
            (lens_j + t)[:, None], step_mask, caches)
        step_logits.append(np.asarray(logits[:, -1], np.float64))
    full = np.zeros((rows, total), np.int32)
    for r in range(rows):
        full[r, :lens[r]] = ids[r, :lens[r]]
        full[r, lens[r]:lens[r] + steps] = tokens[r]
    read_at = (np.asarray(lens)[:, None] - 1) + np.arange(steps)[None, :]
    want = ref.forward(clf.params, HF, full, read_at)["logits"]
    tol = ref.TEST_TOLERANCE["last_logit_median"]
    for t in range(steps):
        assert np.median(np.abs(step_logits[t] - want[:, t])) < tol, t
        chosen = want[np.arange(rows), t, tokens[:, t]]
        assert (want[:, t].max(-1) - chosen < 4 * tol).all(), t


# ------------------------------------- the prefill's compact feed-forward

def _ragged_step(clf, lengths, width=64, seed=11):
    """Token ids of a ``[rows, width]`` step whose rows have ``lengths``
    (BOS first, pad behind), as the tokenizer would hand them over."""
    rng = np.random.default_rng(seed)
    ids = np.zeros((len(lengths), width), np.int32)
    for row, n in enumerate(lengths):
        ids[row, :n] = rng.integers(16, clf.config.vocab_size, size=n)
        ids[row, 0] = clf.tokenizer.bos_id
    return ids, np.asarray(lengths, np.int32)


# a full row and a one-token row (N = 125 of 256, rung 128); real tokens
# that are exactly a rung (96); one token past a rung (97 -> 128)
_STEPS = {"ragged": [64, 1, 23, 37], "exact-rung": [40, 9, 30, 17],
          "one-past-a-rung": [40, 9, 30, 18]}


def _bf16_steps(got, want, steps=1):
    """Whether ``got`` is within ``steps`` bfloat16 steps of ``want``."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return (np.abs(got - want)
            <= steps * 2.0 ** -7 * np.maximum(np.abs(want), 1.0)).all()


@pytest.mark.parametrize("lengths", list(_STEPS.values()), ids=list(_STEPS))
def test_compact_prefill_equals_the_full_one_on_every_real_position(
        clf, lengths):
    """The model's forward with ``prefill_capacity`` (feed-forward halves
    on the real positions) against the same call without: the blocks'
    output and the latent cache on every real position, the experts they
    chose, and through the scoring program the label scores."""
    ids, lens = _ragged_step(clf, lengths)
    rows, width = ids.shape
    capacity = compact_capacity(int(lens.sum()), rows, width)
    assert capacity == {125: 128, 96: 96, 97: 128}[int(lens.sum())]
    positions = jnp.broadcast_to(jnp.arange(width), (rows, width))
    lens_j = jnp.asarray(lens)
    kv = jnp.arange(width + 8)[None, None, None, :]
    mask = causal_mask(width, width + 8, 0) & (
        kv < lens_j[:, None, None, None])

    def forward(**declared):
        (logits, caches), sown = clf.model.apply(
            {"params": clf.params}, jnp.asarray(ids), positions, mask,
            init_caches(clf.config, rows, width + 8),
            mutable=["intermediates"], **declared)
        chosen = np.stack([
            np.asarray(sown["intermediates"][f"layer_{i}"]
                       ["feed_forward_moe"]["chosen"][0]) for i in (1, 2)])
        return np.asarray(logits), caches, chosen

    full, full_caches, full_chosen = forward(prefill_lengths=lens_j)
    got, caches, chosen = forward(prefill_lengths=lens_j,
                                  prefill_capacity=capacity)
    real = np.arange(width)[None, :] < lens[:, None]
    # the final norm and the head of every real position read the blocks'
    # output there: equal logits are equal block outputs
    assert _bf16_steps(got[real], full[real])
    assert np.abs(got[real] - full[real]).max() < 0.05
    for layer, (a, b) in enumerate(zip(caches, full_caches)):
        assert _bf16_steps(np.asarray(a.latents)[:, :width][real],
                           np.asarray(b.latents)[:, :width][real]), layer
        assert _bf16_steps(np.asarray(a.rope_keys)[:, :width][real],
                           np.asarray(b.rope_keys)[:, :width][real]), layer
    assert (chosen[:, real] == full_chosen[:, real]).all()
    assert (chosen[:, ~real] == 0).all()

    labels = (jnp.asarray(clf._label_ids), jnp.asarray(clf._label_lens))
    want, want_stats = clf._score_labels(
        clf.params, jnp.asarray(ids), lens_j, *labels)
    scores, stats = clf._score_labels(
        clf.params, jnp.asarray(ids), lens_j, *labels,
        prefill_capacity=capacity)
    assert _bf16_steps(scores, want)
    assert (np.asarray(stats["chosen"])[:, real]
            == np.asarray(want_stats["chosen"])[:, real]).all()
    assert (np.asarray(stats["chosen_labels"])
            == np.asarray(want_stats["chosen_labels"])).all()
    # (b) the load the step reports is the real positions': N * top_k
    assert (np.asarray(stats["expert_load_mean"]) * clf.config.n_experts
            ).tolist() == [lens.sum() * 2] * 2
    assert (np.asarray(want_stats["expert_load_mean"])
            * clf.config.n_experts).tolist() == [rows * width * 2] * 2


# ------------------- the prefill's compact stream, embedding to last norm

# 4 x 512: a width the packed kernel takes, rungs of 256 slots.  A full row,
# a one-token row, a block and a block + 1 (698 real of 768 slots); rows
# that fill their rung to the last slot (1,024 of 1,024)
_WIDE_STEPS = {"ragged": [300, 1, 256, 141],
               "fills-the-capacity": [512, 255, 1, 256]}


@pytest.mark.parametrize("lengths", list(_WIDE_STEPS.values()),
                         ids=list(_WIDE_STEPS))
def test_compact_stream_equals_the_padded_prefill_on_every_real_position(
        clf, clf32, lengths, monkeypatch):
    """At a width and a rung the packed prefill kernel takes, a prefill
    that declares a ``prefill_capacity`` keeps its hidden state on the
    compact token set from the embedding to the last norm
    (``llama.runs_compact``).  Against the padded prefill (``runs_compact``
    answering no: every position through every layer, the blocked form
    under the mask), both in float32 (``clf32``, caches too): the head's
    logits at each row's last position, the cached ``latents`` and
    ``rope_keys`` and the chosen experts on every real position, zeros in
    the cache behind a row's length.  As served
    (bfloat16), with the capacity declared and without (the stream on the
    step's rows x width slots): through the scoring program the label
    scores, and the compile records name what ran; through
    ``generate_scan_program`` the same tokens."""
    from music_analyst_tpu.models import compact_stream

    ids, lens = _ragged_step(clf, lengths, width=512)
    rows, width = ids.shape
    capacity = compact_capacity(int(lens.sum()), rows, width)
    assert capacity == {698: 768, 1024: 1024}[int(lens.sum())]
    assert llama.runs_compact(clf.config, ids.shape, capacity)
    positions = jnp.broadcast_to(jnp.arange(width), (rows, width))
    lens_j = jnp.asarray(lens)
    kv = jnp.arange(width + 8)[None, None, None, :]
    mask = causal_mask(width, width + 8, 0) & (
        kv < lens_j[:, None, None, None])

    def forward(**declared):
        (logits, caches), sown = clf32.model.apply(
            {"params": clf.params}, jnp.asarray(ids), positions, mask,
            init_caches(clf.config, rows, width + 8, jnp.float32),
            last_position=lens_j - 1, prefill_lengths=lens_j,
            mutable=["intermediates"], **declared)
        chosen = np.stack([
            np.asarray(sown["intermediates"][f"layer_{i}"]
                       ["feed_forward_moe"]["chosen"][0]) for i in (1, 2)])
        return np.asarray(logits), caches, chosen

    with monkeypatch.context() as patched:
        patched.setattr(compact_stream, "runs_compact", lambda *a: False)
        full, full_caches, full_chosen = forward()
    got, caches, chosen = forward(prefill_capacity=capacity)
    real = np.arange(width)[None, :] < lens[:, None]
    assert got.shape == full.shape == (rows, 1, clf.config.vocab_size)
    # a row that starts inside a kernel block sums its tiles in another
    # order: bfloat16 roundings apart, layer after layer
    assert np.abs(got - full).max() < 0.05
    for layer, (a, b) in enumerate(zip(caches, full_caches)):
        for name in ("latents", "rope_keys"):
            mine = np.asarray(getattr(a, name), np.float32)[:, :width]
            apart = np.abs(mine[real] - np.asarray(
                getattr(b, name), np.float32)[:, :width][real])
            assert apart.max() < 0.1 and apart.mean() < 0.002, (layer, name)
            assert not mine[~real].any()
    # a tie between two experts may fall the other way after a rounding
    assert (chosen[:, real] == full_chosen[:, real]).mean() > 0.99
    assert (chosen[:, ~real] == 0).all()

    labels = (jnp.asarray(clf._label_ids), jnp.asarray(clf._label_lens))
    want, _ = clf._score_labels(clf.params, jnp.asarray(ids), lens_j,
                                *labels)
    scores, stats = clf._score_labels(
        clf.params, jnp.asarray(ids), lens_j, *labels,
        prefill_capacity=capacity)
    assert np.abs(np.asarray(scores) - np.asarray(want)).max() < 0.03
    for key, record in clf._score_labels.records.items():
        if f"int32[{rows}, {width}]" in key:    # both: the stream
            assert record.attention_paths == {"mla_flash_packed": 3}
            assert record.traced_paths["mla.compact"] == 3
            assert record.traced_paths["moe.compact"] == 2
    assert (np.asarray(stats["expert_load_mean"]) * clf.config.n_experts
            ).tolist() == [lens.sum() * 2] * 2

    def tokens(**static):
        return np.asarray(clf._generate_scan(
            clf.params, jnp.asarray(ids), lens_j, 4, early_exit=False,
            **static))

    assert (tokens(prefill_capacity=capacity) == tokens()).all()


def test_who_keeps_the_padded_stream():
    """``runs_compact`` is decided by what the call shows: blocks that
    take the stream, a width and a slot count the packed kernels take,
    full rows included, and every rung of an odd number of rows at such a
    width (``compact_capacity`` rounds it to whole tiles).  Everyone else
    keeps the ``[B, S, dim]`` stream."""
    tiny = PRESETS["kanana-tiny"]()
    assert llama.runs_compact(tiny, (32, 1024), 12288)
    assert llama.runs_compact(tiny, (4, 512), 768)
    assert not llama.runs_compact(tiny, (32, 1024), None)        # a mesh
    assert llama.runs_compact(tiny, (32, 1024), 32 * 1024)       # full rows
    assert not llama.runs_compact(tiny, (4, 64), 128)     # blocked width
    assert not llama.runs_compact(tiny, (4, 256), 512)    # one tile a row
    assert not llama.runs_compact(tiny, (13, 512), 832)   # not whole tiles
    assert all(llama.runs_compact(tiny, (13, 512),
                                  compact_capacity(n, 13, 512))
               for n in range(0, 13 * 512 + 1, 7))
    # grouped-query blocks alone: a block-diffusion prefill takes the
    # stream (tests/test_sdar.py), an autoregressive decoder does not
    assert llama.runs_compact(PRESETS["sdar-tiny"](), (32, 1024), 12288)
    assert not llama.runs_compact(LlamaConfig.tiny(), (32, 1024), 12288)


@pytest.mark.parametrize("lengths", [[512] * 4, [512, 40, 300, 256] * 3 + [7]],
                         ids=["full-rows", "13-rows"])
def test_a_step_of_full_rows_runs_the_program_without_lengths(clf, clf32,
                                                              lengths):
    """A step that declares its rows' lengths runs the compact stream at a
    kernel width whatever its rung: full rows (the rung is the step
    itself, and a caller that declares no capacity compiles that program
    text for text) and an odd number of rows (13 x 512, rungs of whole
    tiles).  Its logits at every row's last position equal the padded
    program's, the one a caller that declares no lengths compiles (the
    blocked form under the mask), both in float32 (``clf32``, caches
    too); and at a
    narrow width the step of full rows is that padded program itself."""
    ids, lens = _ragged_step(clf, lengths, width=512)
    rows, width = ids.shape
    capacity = compact_capacity(int(lens.sum()), rows, width)
    assert capacity == ({4: 2048, 13: 4352}[rows])
    assert llama.runs_compact(clf.config, ids.shape, capacity)
    args = (clf.params, jnp.asarray(ids), jnp.asarray(lens),
            jnp.asarray(clf._label_ids), jnp.asarray(clf._label_lens))

    def text(**static):
        return clf._score_labels.lower(*args, **static).as_text()

    if rows * width == capacity:
        assert text(prefill_capacity=capacity) == text()
    positions = jnp.broadcast_to(jnp.arange(width), (rows, width))
    lens_j = jnp.asarray(lens)
    kv = jnp.arange(width + 8)[None, None, None, :]
    mask = causal_mask(width, width + 8, 0) & (
        kv < lens_j[:, None, None, None])

    def logits(**declared):
        return np.asarray(jax.jit(lambda p: clf32.model.apply(
            {"params": p}, jnp.asarray(ids), positions, mask,
            init_caches(clf.config, rows, width + 8, jnp.float32),
            last_position=lens_j - 1, **declared)[0])(clf.params))

    got = logits(prefill_lengths=lens_j, prefill_capacity=capacity)
    want = logits()
    assert got.shape == want.shape == (rows, 1, clf.config.vocab_size)
    assert np.abs(got - want).max() < 0.05

    # at a width the kernels refuse the rung of full rows is the step, and
    # the traced program is the uncompacted one
    ids, lens = _ragged_step(clf, [64, 64, 64, 64])
    assert compact_capacity(int(lens.sum()), *ids.shape) == ids.size
    positions = jnp.broadcast_to(jnp.arange(64), (4, 64))
    mask = causal_mask(64, 64, 0)

    def block_text(**declared):
        return jax.jit(lambda p, x: clf.model.apply(
            {"params": p}, x, positions, mask, **declared)[0]).lower(
                clf.params, jnp.asarray(ids)).as_text()

    assert block_text(prefill_lengths=jnp.asarray(lens),
                      prefill_capacity=ids.size) == block_text()
    # the staged hooks hand the program a rung
    assert clf.transfer(clf.prepare(["la"] * 2))[3][2] < 2 * 64


def _traced_growth(fn):
    """How often a trace took ``moe.grouped`` and ``moe.compact`` while
    ``fn`` ran."""
    from music_analyst_tpu.telemetry import get_telemetry

    tel = get_telemetry()
    names = ("traced.moe.grouped", "traced.moe.compact")
    before = [tel.counters.get(name, 0) for name in names]
    fn()
    return tuple(tel.counters.get(name, 0) - b
                 for name, b in zip(names, before))


@pytest.mark.parametrize("path", ["label_passes", "decode_step", "mesh",
                                  "no_capacity", "generate_prefill"])
def test_where_the_compact_path_must_and_must_not_engage(clf, path):
    """Only a prefill that declares lengths and a capacity runs compact:
    the vmapped label continuations, a decode step, a forward under a mesh
    of more than one device (lengths withheld) and a caller that declares
    no capacity trace ``moe.grouped`` without ``moe.compact``."""
    # a width no other test traces: a traced program is not traced again
    ids, lens = _ragged_step(clf, [48, 1, 23, 37], width=48)
    assert compact_capacity(int(lens.sum()), *ids.shape) == 120
    labels = (jnp.asarray(clf._label_ids), jnp.asarray(clf._label_lens))
    if path == "label_passes":
        # two routed layers: the prefill's two take it, the label passes'
        # two (one vmapped trace of three continuations) do not
        grouped, compact = _traced_growth(lambda: clf._score_labels.lower(
            clf.params, jnp.asarray(ids), jnp.asarray(lens), *labels,
            prefill_capacity=120))
        assert (grouped, compact) == (4, 2)
    elif path == "no_capacity":
        grouped, compact = _traced_growth(lambda: clf._score_labels.lower(
            clf.params, jnp.asarray(ids), jnp.asarray(lens), *labels))
        assert (grouped, compact) == (4, 0)
    elif path == "decode_step":
        caches = init_caches(clf.config, 4, 56)
        grouped, compact = _traced_growth(lambda: clf._decode_step.lower(
            clf.params, jnp.asarray(ids[:, :1]), jnp.asarray(lens), caches))
        assert (grouped, compact) == (2, 0)
    elif path == "generate_prefill":
        # the scan program's prefill declares both; its decode steps (one
        # traced scan body) neither
        grouped, compact = _traced_growth(lambda: clf._generate_scan.lower(
            clf.params, jnp.asarray(ids), jnp.asarray(lens), 4,
            early_exit=False, prefill_capacity=120))
        assert (grouped, compact) == (4, 2)
    else:
        from music_analyst_tpu.parallel.mesh import MeshSpec, build_mesh

        mesh = build_mesh(MeshSpec((("dp", 1), ("ep", 2), ("tp", 1))),
                          devices=jax.devices()[:2])
        meshed = LlamaZeroShotClassifier(
            config=clf.config, mesh=mesh, max_prompt_len=clf.max_prompt_len)
        transferred = meshed.transfer(meshed.prepare(LYRICS[:4]))
        assert transferred[3][2] is None  # no capacity where none is read
        grouped, compact = _traced_growth(
            lambda: meshed.collect(meshed.launch(transferred)))
        assert (grouped, compact) == (4, 0)
        record = list(meshed._score_labels.records.values())[-1]
        assert "moe.compact" not in record.traced_paths
        # and even handed a capacity, withheld lengths keep the full path
        grouped, compact = _traced_growth(
            lambda: meshed._score_labels.lower(
                meshed.params, jnp.asarray(ids), jnp.asarray(lens), *labels,
                prefill_capacity=120))
        assert (grouped, compact) == (4, 0)


# ------------------------------------------------------ runtimes, entry points

@pytest.mark.parametrize("runtime", ["paged_runtime", "slot_runtime"])
def test_decode_runtimes_refuse_the_latent_cache(clf, runtime):
    from music_analyst_tpu.serving import decode_runtime

    with pytest.raises(NotImplementedError, match="latent cache"):
        getattr(decode_runtime, runtime)(clf)
    assert "latent cache" in clf.decode_runtime_refusal
    assert LlamaZeroShotClassifier(
        config=LlamaConfig.tiny()).decode_runtime_refusal is None


def test_staged_hooks_equal_classify_batch_and_count_the_step(clf):
    from music_analyst_tpu.telemetry import get_telemetry

    tel = get_telemetry()
    before = dict(tel.counters)
    with tel.span("compute") as span:
        labels = clf.collect(clf.launch(clf.transfer(clf.prepare(LYRICS))))
    assert labels == clf.classify_batch(LYRICS)
    assert labels[-1] == "Neutral"  # the empty lyric
    ids, lens = _prompts(clf, LYRICS)
    rows, width = ids.shape
    # a label is its word then EOS; the EOS's own forward is read by nothing
    assert clf._label_lens.tolist() == [2, 2, 2]
    assert (clf._label_ids[:, 1] == clf.tokenizer.eos_id).all()
    real = int(lens.sum()) + rows * 3
    assert tel.counters["decoder.tokens_real"] - before.get(
        "decoder.tokens_real", 0) == 2 * real
    # what went through the layers: the compact token set's slots (the
    # step's width and rung are ones the packed prefill takes), not the
    # step's rows x width, then the one position a label whose forward is
    # read (the word's; the table is two wide, not padded to 8)
    capacity = compact_capacity(int(lens.sum()), rows, width)
    assert llama.runs_compact(clf.config, (rows, width), capacity)
    assert tel.counters["decoder.tokens_computed"] - before.get(
        "decoder.tokens_computed", 0) == 2 * (capacity + rows * 3 * 1)
    assert span.attrs["rows"] == rows and span.attrs["width"] == width
    assert span.attrs["tokens_real"] == int(lens.sum())
    assert span.attrs["token_pairs"] == int((lens * (lens + 1) // 2).sum())
    assert (span.attrs["label_positions"],
            span.attrs["label_positions_real"]) == (3, 3)
    ratios = span.attrs["expert_load_max_over_mean"]
    assert len(ratios) == 2 and all(1.0 <= r <= 8.0 for r in ratios)
    # the cache as allocated: 8 label slots behind the prompt's width
    assert tel.gauges["latent_cache_bytes"] == rows * (width + 8) * 3 * 2 * 24
    # two steps of one shape: the prefill's feed-forward halves ran on the
    # rung that holds the real tokens, every REAL position's assignments
    # are counted (top_k a routed layer) and the rows the grouped matmuls
    # ran are the rung's, fillers included, none of them on the grouped
    # matmul kernel (widths under a lane tile:
    # models/moe.grouped_kernel_tiles)
    assert int(lens.sum()) <= capacity < rows * width
    assert span.attrs["moe_capacity"] == capacity
    grew = {name: tel.counters[name] - before.get(name, 0)
            for name in ("moe.assignments", "moe.rows_computed",
                         "moe.rows_kernel")}
    assert grew == {"moe.assignments": 2 * 2 * int(lens.sum()) * 2,
                    "moe.rows_computed": 2 * 2 * capacity * 2,
                    "moe.rows_kernel": 0}


# ------------------------------------------- the label continuations' width

def _tables(vocab_size: int) -> dict:
    """Label tables by their lengths: a word then EOS (the hash-word
    tokenizer's), the byte tokenizer's ``Positive`` / ``Neutral`` /
    ``Negative``, and one-token labels that nothing closes."""
    from music_analyst_tpu.models.tokenization import (
        ByteTokenizer,
        HashWordLMTokenizer,
    )

    words = llama._label_table(HashWordLMTokenizer(vocab_size))
    return {"2-2-2": words,
            "8-7-8": llama._label_table(ByteTokenizer(vocab_size)),
            "1-1-1": (words[0][:, :1], np.ones(3, np.int32))}


@pytest.mark.parametrize("lengths", ["2-2-2", "8-7-8", "1-1-1"])
@pytest.mark.parametrize("model", ["kanana-tiny", "ling-tiny", "llama3-tiny"])
def test_label_scores_equal_those_of_the_table_padded_to_eight(model,
                                                               lengths):
    """A continuation runs the table's width less one (the positions
    whose forward some label reads: 1 for ``word + EOS``, 7 for the byte
    tokenizer, none for one-token labels) and scores as the same model
    does on the table padded to 8, through a latent cache
    (``kanana-tiny``), recurrent states beside one (``ling-tiny``: one
    label after the other) and a key-value cache (``llama3-tiny``); the
    experts the positions ran are the same, and ``-1`` stands at the last
    position, which ran none."""
    served = _backend(model)
    ids, lens = _prompts(served, _lyrics(3, 5) + [""])
    table, label_lens = _tables(served.config.offline_vocab_size)[lengths]
    assert label_lens.tolist() == [int(n) for n in lengths.split("-")]
    width = table.shape[1]
    assert width == label_lens.max()
    padded = np.zeros((3, 8), table.dtype)
    padded[:, :width] = table

    def run(label_ids):
        scores, stats = served._score_labels(
            served.params, jnp.asarray(ids), jnp.asarray(lens),
            jnp.asarray(label_ids), jnp.asarray(label_lens))
        return np.asarray(scores, np.float64), stats

    got, stats = run(table)
    want, want_stats = run(padded)
    assert np.isfinite(got).all() and got.shape == (len(lens), 3)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    if served.config.routed_experts:
        chosen = np.asarray(stats["chosen_labels"])
        wide = np.asarray(want_stats["chosen_labels"])
        layers = chosen.shape[1]
        assert chosen.shape == (3, layers, len(lens), width,
                                served.config.moe_top_k)
        assert (chosen[:, :, :, :width - 1]
                == wide[:, :, :, :width - 1]).all()
        assert (chosen[:, :, :, width - 1] == -1).all()
        assert (wide[:, :, :, 7] == -1).all() and (wide[..., :7, :] >= 0).all()
    else:
        assert "chosen_labels" not in stats
    if served.config.experts_held is not None:
        assert int(stats["label_assignments_held"]) == int(
            want_stats["label_assignments_held"])
    with pytest.raises(ValueError, match="label slots"):
        run(np.zeros((3, 9), table.dtype))


@pytest.mark.parametrize("model,ran,read", [
    ("kanana-tiny", 3, 3), ("ling-tiny", 3, 3), ("llama3-tiny", 21, 20)])
def test_a_step_counts_the_label_positions_it_ran(model, ran, read):
    """The ``compute`` span and the counters say what a continuation ran:
    ``label_positions`` = labels x (the table's width - 1), equal to
    ``label_positions_real`` where the labels are equally long (the
    cells' ``word + EOS``; the byte tokenizer's ``Neutral`` is a byte
    shorter), and one state step a KDA layer a position; two steps of one
    shape are one scoring program."""
    from music_analyst_tpu.telemetry import get_telemetry

    served = _backend(model)
    lyrics = _lyrics(5, 6)
    ids, lens = _prompts(served, lyrics)
    rows, width = ids.shape
    tel = get_telemetry()
    before = dict(tel.counters)
    programs = set(served._score_labels.records)
    with tel.span("compute") as span:
        for _ in range(2):
            served.collect(served.launch(served.transfer(
                served.prepare(lyrics))))
    assert len(set(served._score_labels.records) - programs) <= 1
    assert (span.attrs["label_positions"],
            span.attrs["label_positions_real"]) == (ran, read)
    assert ran == 3 * (served._label_ids.shape[1] - 1)

    def grew(name):
        return tel.counters.get(name, 0) - before.get(name, 0)

    capacity = compact_capacity(int(lens.sum()), rows, width)
    through = (capacity if llama.runs_compact(
        served.config, (rows, width), capacity) else rows * width)
    assert grew("decoder.tokens_computed") == 2 * (through + rows * ran)
    assert grew("decoder.tokens_real") == 2 * (int(lens.sum()) + rows * read)
    kda = served.config.kda_layers
    assert grew("kda.state_steps") == 2 * rows * ran * kda
    if served.config.latent_cache:
        cfg = served.config
        assert tel.gauges["latent_cache_bytes"] == (
            rows * (width + llama.MAX_LABEL_TOKENS) * (cfg.n_layers - kda)
            * 2 * (cfg.kv_lora_rank + cfg.qk_rope_head_dim))


def test_sentiment_cli_end_to_end(tmp_path, fixture_csv):
    from music_analyst_tpu.cli.main import main

    out = tmp_path / "out"
    assert main(["sentiment", str(fixture_csv), "--model", "kanana-tiny",
                 "--output-dir", str(out), "--batch-size", "4"]) == 0
    totals = json.loads((out / "sentiment_totals.json").read_text())
    assert sum(totals.values()) == 8
    manifest = json.loads((out / "run_manifest.json").read_text())
    counters = manifest["counters"]
    assert counters["decoder.tokens_computed"] > counters[
        "decoder.tokens_real"] > 0
    assert counters["traced.mla.absorbed"] and counters["moe.assignments"]
    compute = [json.loads(line) for line in
               (out / "telemetry.jsonl").read_text().splitlines()]
    compute = [e for e in compute
               if e.get("type") == "span" and e["name"] == "compute"]
    assert len(compute) == 2 and all(
        {"rows", "width", "tokens_real"} <= set(e["attrs"]) for e in compute)


def test_serve_sentiment_op_runs_and_generate_is_refused(clf):
    from music_analyst_tpu.models.backend import ModelResidency
    from music_analyst_tpu.serving.server import build_resident_ops

    ops = build_resident_ops(ModelResidency(model="kanana-tiny", backend=clf))
    replies = ops["sentiment"](["la la love", ""])
    assert [r["label"] for r in replies][1] == "Neutral"
    from music_analyst_tpu.serving.decode_loop import ContinuousScheduler

    with pytest.raises(NotImplementedError, match="latent cache"):
        ContinuousScheduler(clf, n_slots=2)


def test_sharded_equals_unsharded(clf):
    from music_analyst_tpu.parallel.mesh import MeshSpec, build_mesh
    from music_analyst_tpu.parallel.sharding import partition_specs

    mesh = build_mesh(MeshSpec((("dp", 1), ("ep", 2), ("tp", 2))),
                      devices=jax.devices()[:4])
    sharded = LlamaZeroShotClassifier(
        config=clf.config, mesh=mesh, max_prompt_len=clf.max_prompt_len)
    specs = partition_specs(sharded.params)
    moe = specs["layer_1"]["feed_forward_moe"]
    assert tuple(moe["gate_experts"]) == ("ep", None, "tp")
    assert tuple(moe["down_experts"]) == ("ep", "tp", None)
    assert tuple(moe["router"]) == ()
    attn = specs["layer_1"]["attention"]
    assert tuple(attn["kv_b_proj"]["kernel"]) == (None, "tp", None)
    assert tuple(attn["q_proj"]["kernel"]) == (None, "tp", None)
    assert tuple(attn["kv_a_proj"]["kernel"]) == ()
    gate = sharded.params["layer_1"]["feed_forward_moe"]["gate_experts"]
    assert gate.sharding.shard_shape(gate.shape) == (4, 64, 16)
    ids, lens = _prompts(clf, LYRICS[:4])
    want = _program_scores(clf, clf.params, ids, lens)
    got = _program_scores(sharded, sharded.params, ids, lens)
    # same seeded weights, same mathematics; the partitioned sums differ in
    # bfloat16 rounding only
    assert np.median(np.abs(got - want)) < ref.TEST_TOLERANCE[
        "label_score_median"]
    # a 512-wide step: one device runs the prefill kernel; under the mesh
    # the call would be opaque to the partitioner (every chip all the
    # heads), so the meshed program keeps the XLA form, which it splits
    assert ids.shape[1] == 512
    meshed = list(sharded._score_labels.records.values())[-1]
    assert any(r.attention_paths == {"mla_flash_packed": 3}
               for r in clf._score_labels.records.values())
    assert meshed.attention_paths == {"mla_blocked": 3}
    assert meshed.traced_paths["mla.expanded"] == 3
