"""The ``ling_hybrid`` decoder kinds (Kimi Delta Attention on a recurrent
state, five layers to one gated latent-attention layer, sigmoid-routed
experts chosen inside the best groups of which this chip holds a share)
against the plain float32 reference ``perfbench/reference/ling_hybrid_f32.py``,
at the ``ling-tiny`` size with seeded weights.

Layer tests run the program's modules in float32 on the reference's own
inputs (the XLA forms: the kernel's MXU operands are bfloat16 whatever the
model's dtype), so they hold the equations.  The three forms of the
recurrence are held to each other at decays down to ``e^-5`` a step.  The
reference itself is held to two independent sources that ``transformers``
ships.  The end-to-end tests run the system as it is served, bfloat16, the
kernel under the interpreter, and hold it to ``TEST_TOLERANCE``.
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
if os.path.join(REPO, "perfbench") not in sys.path:
    sys.path.insert(0, os.path.join(REPO, "perfbench"))

from reference import ling_hybrid_f32 as ref  # noqa: E402

from music_analyst_tpu.models import llama  # noqa: E402
from music_analyst_tpu.models.kda import (  # noqa: E402
    KimiDeltaAttention,
    RecurrentState,
    causal_conv,
)
from music_analyst_tpu.models.layers import causal_mask  # noqa: E402
from music_analyst_tpu.models.llama import (  # noqa: E402
    PRESETS,
    LlamaConfig,
    init_caches,
)
from music_analyst_tpu.models.mla import LatentCache, MLAttention  # noqa: E402
from music_analyst_tpu.models.compact_stream import (  # noqa: E402
    RealPositions,
    compact_capacity,
)
from music_analyst_tpu.models.moe import (  # noqa: E402
    RoutedMoE,
    route_sigmoid_noaux,
)
from music_analyst_tpu.ops import kda_attention as kda_ops  # noqa: E402

F32_TOL = 2e-4  # float32 program against float32 reference


def _preset(name):
    path = os.path.join(REPO, "music_analyst_tpu", "models", "presets",
                        name + ".json")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def _as_reference_config(preset: dict) -> dict:
    """A preset in the layout of a ``perfbench/configs`` file: what the
    source does not state sits under ``model``."""
    runtime = preset["runtime"]
    return {**preset, "model": {"layer_ids": runtime["layer_ids"],
                                "experts_held": runtime["experts_held"]}}


HF = _as_reference_config(_preset("ling-tiny"))

_WORDS = ("love rain night baby tears dance road fire cold heart sun blue "
          "you me the and never always gone stay").split()


def _lyrics(seed: int, rows: int, longest: int = 400):
    rng = np.random.default_rng(seed)
    return [" ".join(rng.choice(_WORDS, size=int(n)))
            for n in rng.integers(5, longest, size=rows)]


@pytest.fixture(scope="module")
def clf():
    from music_analyst_tpu.engines.sentiment import get_backend

    return get_backend("ling-tiny")


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _hidden(rows, n_tok, dim, seed=0):
    return jax.random.normal(jax.random.key(seed), (rows, n_tok, dim),
                             jnp.float32)


def _kda(cfg: LlamaConfig, **kw):
    return KimiDeltaAttention(
        n_heads=cfg.n_heads, head_dim=cfg.kda_head_dim,
        conv_kernel=cfg.kda_conv_kernel, lower_bound=cfg.kda_lower_bound,
        norm_eps=cfg.rms_norm_eps, dtype=jnp.float32, **kw)


# ----------------------------------------------------------- configuration

def test_presets_are_built_from_their_files(clf):
    cfg = clf.config
    assert [cfg.mixer(i) for i in range(cfg.n_layers)] == [
        "kda", "kda", "kda", "mla"]
    assert [cfg.routed_layer(i) for i in range(4)] == [False, True, True, True]
    assert (cfg.n_experts, cfg.experts_held, cfg.moe_top_k, cfg.n_group,
            cfg.topk_group, cfg.n_shared_experts) == (16, (0, 4), 4, 4, 2, 1)
    assert cfg.mla_output_gate and cfg.recurrent_state and cfg.latent_cache
    big = PRESETS["ling-3.0-flash-vl"]()
    assert [big.mixer(i) for i in range(big.n_layers)] == [
        "kda"] * 6 + ["mla"]           # dense KDA layer 0, then 6..11
    assert (big.dim, big.n_heads, big.hidden_dim, big.kda_head_dim,
            big.kda_conv_kernel, big.kda_lower_bound) == (
                2560, 32, 6144, 128, 4, -5.0)
    assert (big.n_experts, big.experts_held, big.moe_hidden_dim,
            big.moe_top_k, big.n_group, big.topk_group,
            big.n_shared_experts, big.routed_scaling_factor) == (
                512, (0, 128), 768, 8, 8, 4, 1, 2.5)
    assert (big.kv_lora_rank, big.qk_nope_head_dim, big.qk_rope_head_dim,
            big.v_head_dim, big.vocab_size, big.rope_theta) == (
                512, 128, 64, 128, 39296, 6e6)
    assert (big.n_layers, big.first_k_dense_replace, big.kda_layers) == (
        7, 1, 6)
    assert big.param_dtype == big.dtype == "bfloat16"
    assert (big.prompt_width_floor, cfg.prompt_width_floor) == (1024, 64)


def test_published_keys_are_the_catalogs_but_for_the_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Ling-3.0-flash-VL")
    preset = _preset("ling-3.0-flash-vl")
    assert preset["_source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if preset[k] != v}
    assert changed == {"num_hidden_layers", "first_k_dense_replace",
                       "vocab_size"}
    assert row["config"]["vocab_size"] == 4 * preset["vocab_size"]
    assert row["config"]["num_experts"] == 4 * preset["runtime"][
        "experts_held"][1]


_TINY = _preset("ling-tiny")


@pytest.mark.parametrize("key,value", [
    ("expert_swiglu_limit_list", [0, 0, 0, 0, 4, 0]),
    ("share_expert_swiglu_limit_list", [0, 0, 0, 5, 0, 0]),
    ("use_nGPT", True), ("value_norm", True), ("up_proj_norm", True),
    ("scale_router_input", True), ("use_kda_lora", True),
    ("no_kda_lora", False), ("mtp_use_kda", True), ("use_mla_nope", True),
    ("q_lora_rank", 1536), ("score_function", "softmax"),
    ("moe_router_enable_expert_bias", False),
    ("gated_attention_proj_granularity_type", "elementwise"),
    ("linear_silu", False), ("kda_safe_gate", False),
    ("group_norm_size", 4), ("num_kv_heads_for_linear_attn", 2),
    ("use_qk_norm", False), ("rotary_dim", 16),
    ("rope_scaling", {"type": "yarn"}), ("tie_word_embeddings", True),
])
def test_from_hf_config_refuses_by_name_what_it_cannot_run(key, value):
    with pytest.raises(ValueError, match=key):
        LlamaConfig.from_hf_config({**_TINY, key: value},
                                   **_TINY["runtime"])


def test_a_swiglu_limit_is_refused_only_in_a_layer_that_is_kept():
    """The published lists are non-zero from layer 34 on; the preset keeps
    layers 0 and 6..11 and carries the lists whole."""
    preset = _preset("ling-3.0-flash-vl")
    assert any(preset["expert_swiglu_limit_list"])
    runtime = dict(preset["runtime"])
    LlamaConfig.from_hf_config(preset, **runtime)
    runtime["layer_ids"] = [0, 30, 31, 32, 33, 34, 35]
    with pytest.raises(ValueError, match="swiglu_limit_list"):
        LlamaConfig.from_hf_config(preset, **runtime)


def test_caches_are_of_each_layers_kind(clf):
    caches = init_caches(clf.config, 3, 72)
    kinds = [type(c) for c in caches]
    assert kinds == [RecurrentState] * 3 + [LatentCache]
    assert caches[0].state.shape == (3, 4, 16, 16)
    assert caches[0].state.dtype == jnp.float32
    assert caches[0].conv.shape == (3, 3, 3 * 4 * 16)
    assert caches[3].latents.shape == (3, 72, 16)
    assert caches[0].with_length(5) is caches[0]


def test_init_draws_the_decay_inside_its_range(clf):
    p = clf.params["layer_1"]["attention"]
    assert p["A_log"].dtype == p["dt_bias"].dtype == jnp.float32
    a = np.exp(np.asarray(p["A_log"]))
    assert (a >= 1).all() and (a <= 16).all()
    dt = np.log1p(np.exp(np.asarray(p["dt_bias"], np.float64)))  # softplus
    assert dt.min() >= 1e-3 * 0.99 and dt.max() <= 1e-1 * 1.01
    conv = np.asarray(p["q_conv"], np.float32)
    assert conv.shape == (4, 64) and np.abs(conv).max() <= 0.5
    experts = clf.params["layer_1"]["feed_forward_moe"]
    assert experts["gate_experts"].shape == (4, 64, 32)   # the 4 held
    assert experts["router"].shape == (64, 16)            # of the 16 routed
    assert experts["e_score_correction_bias"].shape == (16,)


# -------------------------------------------------------- the recurrence

def _operands(seed, rows, n_tok, heads, dim, floor=False, repeated=False):
    ks = jax.random.split(jax.random.key(seed), 5)
    shape = (rows, n_tok, heads, dim)
    q = jax.random.normal(ks[0], shape)
    k = jax.random.normal(ks[1], shape)
    if repeated:    # every key of a row nearly the same
        k = k[:, :1] + 0.01 * k
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dim ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], shape)
    if floor:       # every channel at the lower bound, every step
        g = jnp.full(shape, -5.0)
    else:           # over the whole range
        g = -5.0 * jax.nn.sigmoid(4.0 * jax.random.normal(ks[3], shape))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3])
                          + (2.0 if repeated else 0.0))
    return q, k, v, g, beta


_CASES = {"whole-range": {}, "at-the-floor": {"floor": True},
          "repeated-keys": {"repeated": True}}


@pytest.mark.parametrize("case", list(_CASES))
def test_chunked_xla_form_is_the_recurrence(case):
    rows, n_tok, heads, dim = 2, 96, 4, 16
    operands = _operands(3, rows, n_tok, heads, dim, **_CASES[case])
    valid = jnp.arange(n_tok)[None, :] < jnp.asarray([70, 96])[:, None]
    start = jax.random.normal(jax.random.key(9), (rows, heads, dim, dim))
    want_o, want_s = kda_ops.kda_recurrent(*operands, start, valid)
    got_o, got_s = kda_ops.kda_chunked_xla(*operands, start, valid)
    # float32's exp of an argument of 75 is good to 75 ulps: 1e-4, not 1e-6
    assert float(jnp.abs(got_o - want_o).max()) < 1e-4
    assert float(jnp.abs(got_s - want_s).max()) < 1e-4
    # a token that does not exist leaves the state as it is
    alone_o, alone_s = kda_ops.kda_recurrent(
        *(x[:1, :70] for x in operands), start[:1])
    assert float(jnp.abs(alone_s - want_s[:1]).max()) < 1e-6


@pytest.mark.parametrize("case", list(_CASES))
@pytest.mark.parametrize("form", ["padded", "compact"])
def test_kernel_is_the_recurrence_on_bfloat16_operands(case, form):
    """The kernel against the token-by-token form in float32 on the SAME
    bfloat16 operands, rows padded to ``[B, S]`` and rows laid one behind
    the other with nothing aligned: outputs on the real slots and each
    row's final state, within bfloat16's rounding of the MXU operands."""
    rows, n_tok, heads, dim = 4, 96, 4, 16
    lens = np.asarray([70, 3, 96, 33])
    bf = jnp.bfloat16
    q, k, v, g, beta = _operands(5, rows, n_tok, heads, dim, **_CASES[case])
    q, k, v = (x.astype(bf) for x in (q, k, v))
    lens_d = jnp.asarray(lens, jnp.int32)
    valid = jnp.arange(n_tok)[None, :] < lens_d[:, None]
    zeros = jnp.zeros((rows, heads, dim, dim))
    want_o, want_s = kda_ops.kda_recurrent(q, k, v, g, beta, zeros, valid)

    def flat(x):
        return x.reshape(rows * n_tok, -1)

    if form == "padded":
        starts = jnp.arange(rows, dtype=jnp.int32) * n_tok
        got_o, got_s = kda_ops.kda_chunked(
            flat(q), flat(k), flat(v), flat(g), flat(beta), starts,
            starts + lens_d, valid.reshape(-1), heads, n_tok)
        got_o = got_o.reshape(want_o.shape).astype(jnp.float32)
    else:
        capacity = 224          # 202 real slots, 22 fillers
        packed = RealPositions.of(lens_d, n_tok, capacity)
        gather = lambda x: packed.gather(x.reshape(rows, n_tok, -1))  # noqa: E731
        got_o, got_s = kda_ops.kda_chunked(
            gather(q), gather(k), gather(v), gather(g), gather(beta),
            packed.start, packed.start + lens_d, packed.valid, heads, n_tok)
        assert bool(jnp.isfinite(got_o.astype(jnp.float32)).all())
        assert not got_o[int(lens.sum()):].any()       # fillers: zeros
        got_o = packed.put_back(got_o.astype(jnp.float32)).reshape(
            want_o.shape)
    real = valid[..., None, None]
    err_o = float(jnp.abs(jnp.where(real, got_o - want_o, 0.0)).max())
    err_s = float(jnp.abs(got_s - want_s).max())
    assert err_o < 0.02 * float(jnp.abs(want_o).max())
    assert err_s < 0.02 * float(jnp.abs(want_s).max())


def test_kernel_rows_do_not_see_their_neighbours_or_the_fillers():
    """A row of the compact stream gives the same outputs and the same
    final state whatever lies before and behind it, within the rounding of
    the kernel's bfloat16 operands: where its first slot falls alike inside
    a 32-slot block (the same chunks) only the running sums it shares a
    block with differ, in their last float32 bits; where it does not, its
    chunks differ, and the middles the decays are taken about."""
    rows, n_tok, heads, dim = 3, 64, 4, 16
    bf = jnp.bfloat16
    q, k, v, g, beta = _operands(7, rows, n_tok, heads, dim)
    q, k, v = (x.astype(bf) for x in (q, k, v))

    def run(lens, capacity, keep):
        lens_d = jnp.asarray(lens, jnp.int32)
        packed = RealPositions.of(lens_d, n_tok, capacity)
        take = lambda x: packed.gather(  # noqa: E731
            x[jnp.asarray(keep)].reshape(len(keep), n_tok, -1))
        o, s = kda_ops.kda_chunked(
            take(q), take(k), take(v), take(g), take(beta), packed.start,
            packed.start + lens_d, packed.valid, heads, n_tok)
        return np.asarray(o, np.float32), np.asarray(s), np.asarray(
            packed.start)

    def apart(a, b):
        return np.abs(a - b).max() / np.abs(b).max()

    o_all, s_all, starts = run([45, 50, 21], 128, [0, 1, 2])
    # another neighbour in front (13 slots, not 45: the same place in a
    # block), more fillers behind: rows 1 and 2 are a bfloat16 ulp away
    o_more, s_more, starts_more = run([13, 50, 21], 160, [0, 1, 2])
    assert apart(s_more[1:], s_all[1:]) < 1e-3
    for row, n in ((1, 50), (2, 21)):
        got = o_more[starts_more[row]:starts_more[row] + n]
        want = o_all[starts[row]:starts[row] + n]
        assert apart(got, want) < 4e-3
        assert (got == want).mean() > 0.9
    # alone from slot 0: other chunks, the same row
    o_one, s_one, _ = run([50], 64, [1])
    assert apart(o_all[starts[1]:starts[1] + 50], o_one[:50]) < 0.02
    assert apart(s_all[1], s_one[0]) < 0.02
    # a neighbour that is NOT ignored would show at once: the states of two
    # different rows are wholly apart
    assert apart(s_all[1], s_all[2]) > 0.5


def test_reference_recurrence_is_transformers_gated_delta_rule():
    """With every channel of a head given the same ``g``, the per-channel
    rule is the scalar-gated delta rule ``transformers`` ships for
    Qwen3-Next: an independent source for the reference's token loop."""
    torch = pytest.importorskip("torch")
    qwen = pytest.importorskip(
        "transformers.models.qwen3_next.modeling_qwen3_next")
    rows, n_tok, heads, dim = 2, 24, 3, 8
    q, k, v, g, beta = _operands(11, rows, n_tok, heads, dim)
    g = jnp.broadcast_to(g[..., :1], g.shape)
    want_o, want_s = qwen.torch_recurrent_gated_delta_rule(
        *(torch.tensor(np.asarray(x, np.float32)) for x in (
            q * dim ** 0.5, k, v, g[..., 0], beta)),
        initial_state=None, output_final_state=True)
    got_o, got_s = ref.delta_rule(q, k, v, g, beta)
    assert np.abs(np.asarray(got_o) - want_o.numpy()).max() < 1e-5
    assert np.abs(np.asarray(got_s) - want_s.numpy()).max() < 1e-5
    # and the program's token-by-token form is the reference's
    mine_o, mine_s = kda_ops.kda_recurrent(
        q, k, v, g, beta, jnp.zeros((rows, heads, dim, dim)))
    assert float(jnp.abs(mine_o - got_o).max()) < 1e-5
    assert float(jnp.abs(mine_s - got_s).max()) < 1e-5


def test_convolution_restarts_at_a_rows_first_slot():
    rows, n_tok, width = 3, 16, 8
    u = jax.random.normal(jax.random.key(2), (rows, n_tok, width))
    w = jax.random.normal(jax.random.key(3), (4, width))
    want = ref.short_conv(u, w)
    assert float(jnp.abs(causal_conv(u, w) - want).max()) < 1e-6
    # the rows one behind the other: a token's position in its own row
    lens = jnp.asarray([16, 5, 9], jnp.int32)
    packed = RealPositions.of(lens, n_tok, 32)
    positions = packed.gather(
        jnp.broadcast_to(jnp.arange(n_tok), (rows, n_tok)))[None]
    got = causal_conv(packed.gather(u)[None], w, positions=positions)[0]
    got = packed.put_back(got)
    real = (jnp.arange(n_tok)[None, :] < lens[:, None])[..., None]
    assert float(jnp.abs(jnp.where(real, got - want, 0.0)).max()) < 1e-6
    # a continuation reads the inputs it was handed
    again = causal_conv(u[:, 10:], w, history=u[:, 7:10])
    assert float(jnp.abs(again - want[:, 10:]).max()) < 1e-6


# ------------------------------------------------------------ layer kinds

@pytest.mark.parametrize("n_tok", [64, 24], ids=["chunked-xla", "recurrent"])
def test_kda_layer_matches_reference(clf, n_tok):
    cfg = clf.config
    p = _f32(clf.params["layer_1"]["attention"])
    h = _hidden(2, n_tok, cfg.dim)
    lens = jnp.asarray([n_tok, n_tok - 19], jnp.int32)
    want, _ = ref.kda_attention(p, h, HF)
    _, want_s = ref.kda_attention(p, h, HF, snapshot_at=lens - 1)
    state = RecurrentState.zeros(2, cfg.n_heads, cfg.kda_head_dim,
                                 dtype=jnp.float32)
    got, new = _kda(cfg).apply({"params": p}, h, None, state,
                               row_lengths=lens)
    real = (jnp.arange(n_tok)[None, :] < lens[:, None])[..., None]
    assert float(jnp.abs(jnp.where(real, got - want, 0.0)).max()) < F32_TOL
    assert float(jnp.abs(new.state - want_s).max()) < F32_TOL
    # a continuation from that state is the rest of the full forward
    more = _hidden(1, 8, cfg.dim, seed=4)
    both = jnp.concatenate([h[:1], more], axis=1)
    want_more, _ = ref.kda_attention(p, both, HF)
    first = RecurrentState(new.state[:1], new.conv[:1])
    got_more, _ = _kda(cfg).apply({"params": p}, more, None, first)
    assert float(jnp.abs(got_more - want_more[:, n_tok:]).max()) < F32_TOL
    # and the state it read is as it was: another continuation forks it
    other, _ = _kda(cfg).apply({"params": p}, more * 2.0, None, first)
    again, _ = _kda(cfg).apply({"params": p}, more, None, first)
    assert np.array_equal(np.asarray(again), np.asarray(got_more))
    assert not np.array_equal(np.asarray(other), np.asarray(got_more))


def test_gated_mla_matches_reference_expanded_and_absorbed(clf):
    cfg = clf.config
    p = _f32(clf.params["layer_3"]["attention"])
    assert p["gate_proj"]["kernel"].shape == (cfg.dim, cfg.n_heads)
    h = _hidden(2, 256, cfg.dim)
    positions = jnp.broadcast_to(jnp.arange(256), (2, 256))
    want, want_c, want_r = ref.mla_attention(p, h, positions, HF)
    mla = MLAttention(
        n_heads=cfg.n_heads, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, rope_theta=cfg.rope_theta,
        rope_interleave=cfg.rope_interleave, max_positions=cfg.max_seq_len,
        norm_eps=cfg.rms_norm_eps, dtype=jnp.float32, output_gate=True)
    cache = LatentCache.zeros(2, 264, cfg.kv_lora_rank, cfg.qk_rope_head_dim,
                              jnp.float32)
    got, cache = mla.apply({"params": p}, h, causal_mask(256, 264, 0),
                           positions, cache)
    assert float(jnp.abs(got - want).max()) < F32_TOL
    assert float(jnp.abs(cache.latents[:, :256] - want_c).max()) < F32_TOL
    assert np.abs(ref.deinterleave(np.asarray(cache.rope_keys[:, :256]))
                  - np.asarray(want_r)).max() < F32_TOL
    # eight more queries on the cache: the absorbed form, gated alike
    more = _hidden(2, 8, cfg.dim, seed=5)
    both = jnp.concatenate([h, more], axis=1)
    want_more, _, _ = ref.mla_attention(
        p, both, jnp.broadcast_to(jnp.arange(264), (2, 264)), HF)
    kv_pos = jnp.arange(264)[None, None, None, :]
    mask = kv_pos <= (256 + jnp.arange(8))[None, None, :, None]
    got_more, _ = mla.apply(
        {"params": p}, more, mask,
        jnp.broadcast_to(256 + jnp.arange(8), (2, 8)),
        cache.with_length(256))
    assert float(jnp.abs(got_more - want_more[:, 256:]).max()) < F32_TOL
    ungated = dataclasses.replace(mla, output_gate=False)
    plain = {k: v for k, v in p.items() if k != "gate_proj"}
    bare, _ = ungated.apply({"params": plain}, h, causal_mask(256, 264, 0),
                            positions, LatentCache.zeros(
                                2, 264, cfg.kv_lora_rank,
                                cfg.qk_rope_head_dim, jnp.float32))
    assert float(jnp.abs(bare - want).max()) > 100 * F32_TOL


# ------------------------------------------------------------- the router

def test_group_limited_choice_is_transformers_router():
    torch = pytest.importorskip("torch")
    deepseek = pytest.importorskip(
        "transformers.models.deepseek_v3.modeling_deepseek_v3")
    from types import SimpleNamespace

    config = SimpleNamespace(
        num_experts_per_tok=4, n_routed_experts=16, routed_scaling_factor=2.5,
        n_group=4, topk_group=2, norm_topk_prob=True, hidden_size=32)
    router = deepseek.DeepseekV3TopkRouter(config)
    rng = np.random.default_rng(0)
    weight = rng.normal(size=(16, 32)).astype(np.float32) * 32 ** -0.5
    bias = rng.normal(size=16).astype(np.float32) * 0.05
    with torch.no_grad():
        router.weight.copy_(torch.tensor(weight))
        router.e_score_correction_bias.copy_(torch.tensor(bias))
    h = rng.normal(size=(200, 32)).astype(np.float32)
    with torch.no_grad():
        want_idx, want_w = router(torch.tensor(h))
    order = np.argsort(want_idx.numpy(), -1)
    want_idx = np.take_along_axis(want_idx.numpy(), order, -1)
    want_w = np.take_along_axis(want_w.numpy(), order, -1)
    hf = {**HF, "num_experts": 16}
    p = {"router": jnp.asarray(weight.T),
         "e_score_correction_bias": jnp.asarray(bias)}
    # the reference's choice and weights, the share being every expert
    chosen, combine, _ = ref.route(p, jnp.asarray(h), hf, (0, 16))
    assert np.array_equal(np.sort(np.asarray(chosen), -1), want_idx)
    assert np.abs(np.take_along_axis(np.asarray(combine), want_idx, -1)
                  - want_w).max() < 1e-5
    # and the program's
    got_idx, got_w = route_sigmoid_noaux(
        jnp.asarray(h @ weight.T), jnp.asarray(bias), 4, 2.5, True, 4, 2)
    order = np.argsort(np.asarray(got_idx), -1)
    assert np.array_equal(np.take_along_axis(np.asarray(got_idx), order, -1),
                          want_idx)
    assert np.abs(np.take_along_axis(np.asarray(got_w), order, -1)
                  - want_w).max() < 1e-5


def test_one_group_is_the_choice_without_groups():
    logits = jax.random.normal(jax.random.key(0), (64, 16))
    bias = 0.05 * jax.random.normal(jax.random.key(1), (16,))
    plain = route_sigmoid_noaux(logits, bias, 4, 2.5)
    for groups in ((1, 1), (4, 4)):     # every group kept hides none
        idx, w = route_sigmoid_noaux(logits, bias, 4, 2.5, True, *groups)
        assert np.array_equal(np.asarray(idx), np.asarray(plain[0]))
        assert np.array_equal(np.asarray(w), np.asarray(plain[1]))
    limited, _ = route_sigmoid_noaux(logits, bias, 4, 2.5, True, 4, 1)
    assert (np.asarray(limited) // 4 == np.asarray(limited[:, :1]) // 4).all()
    with pytest.raises(ValueError, match="topk_group"):
        route_sigmoid_noaux(logits, bias, 4, 2.5, True, 1, 2)


def test_deepseek_v3_presets_take_the_group_keys_now():
    hf = _preset("kanana-tiny")
    cfg = LlamaConfig.from_hf_config(
        {**hf, "n_group": 4, "topk_group": 2}, **hf["runtime"])
    assert (cfg.n_group, cfg.topk_group) == (4, 2)
    plain = LlamaConfig.from_hf_config(hf, **hf["runtime"])
    assert (plain.n_group, plain.topk_group) == (1, 1)


# -------------------------------------------------------------- the share

def _moe(cfg: LlamaConfig, held, dtype=jnp.float32):
    return RoutedMoE(
        cfg.n_experts, cfg.moe_hidden_dim, cfg.moe_top_k,
        n_shared=cfg.n_shared_experts,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob, dtype=dtype,
        n_group=cfg.n_group, topk_group=cfg.topk_group, experts_held=held)


def test_four_shares_add_up_to_the_uncut_layer(clf):
    """The routed parts that four chips holding 4 of the 16 experts each
    give, and what every chip computes alike (the shared expert) counted
    once, are the uncut reference layer."""
    cfg = clf.config
    h = _hidden(2, 40, cfg.dim, seed=8)
    whole = _moe(cfg, None)
    p = _f32(whole.init(jax.random.key(3), h)["params"])
    p["e_score_correction_bias"] = 0.05 * jax.random.normal(
        jax.random.key(4), (16,))
    want, want_chosen, _ = ref.moe_ffn(p, h, {**HF, "num_experts": 16},
                                       (0, 16))
    shared = ref.swiglu(p["shared_experts"], h)

    def part(first):
        mine = {**p, **{name: p[name][first:first + 4] for name in (
            "gate_experts", "up_experts", "down_experts")}}
        out, sown = _moe(cfg, (first, 4)).apply(
            {"params": mine}, h, mutable=["intermediates"])
        sown = sown["intermediates"]
        # the reference given the same share agrees part by part
        alone, _, _ = ref.moe_ffn(mine, h, {**HF, "num_experts": 16},
                                  (first, 4))
        assert float(jnp.abs(out - alone).max()) < F32_TOL
        return out - shared, sown["chosen"][0], sown["expert_load"][0]

    parts = [part(first) for first in (0, 4, 8, 12)]
    total = sum(out for out, _, _ in parts) + shared
    assert float(jnp.abs(total - want).max()) < F32_TOL
    # every share routes over all sixteen and keeps the published four
    for _, chosen, _ in parts:
        assert np.array_equal(np.sort(np.asarray(chosen), -1),
                              np.sort(np.asarray(want_chosen), -1))
    held = sum(int(load.sum()) for _, _, load in parts)
    assert held == 2 * 40 * cfg.moe_top_k
    uncut, _ = whole.apply({"params": p}, h, mutable=["intermediates"])
    assert float(jnp.abs(uncut - want).max()) < F32_TOL


def test_absent_experts_cost_no_group_and_fillers_stay_zero(clf):
    cfg = clf.config
    h = _hidden(3, 32, cfg.dim, seed=6)
    moe = _moe(cfg, (4, 4))
    p = moe.init(jax.random.key(3), h)["params"]
    assert p["gate_experts"].shape[0] == 4
    lens = jnp.asarray([32, 5, 17], jnp.int32)
    packed = RealPositions.of(lens, 32, 64)
    full, _ = moe.apply({"params": p}, h, mutable=["intermediates"])
    got, sown = moe.apply({"params": p}, h, packed,
                          mutable=["intermediates"])
    real = (jnp.arange(32)[None, :] < lens[:, None])[..., None]
    assert float(jnp.abs(jnp.where(real, got - full, 0.0)).max()) < 1e-5
    assert not np.asarray(jnp.where(real, 0.0, got)).any()
    sown = sown["intermediates"]
    assert int(sown["assigned"][0]) == 54 * cfg.moe_top_k
    assert int(sown["expert_load"][0].sum()) <= 54 * cfg.moe_top_k


# ------------------------------------------------ the program, end to end

LYRICS = _lyrics(1, 12) + [""]


def _system(clf, lyrics, probe=None):
    prepared = clf.prepare(lyrics)
    _, ids, lens = prepared
    if probe is not None:
        clf.probe_rows = np.asarray(probe, np.int32)
    handle = clf.launch(clf.transfer(prepared))
    scores = np.asarray(handle[1], np.float64)
    labels = clf.collect(handle)
    return np.asarray(ids), np.asarray(lens), scores, handle[2], labels


def _judged(clf, ids, lens, stats, variant="f32", rows=None):
    tol = ref.TEST_TOLERANCE
    rows = np.arange(len(lens)) if rows is None else np.asarray(rows)
    prefer = ref.prefer_from_system(
        np.asarray(stats["chosen"])[:, rows],
        np.asarray(stats["chosen_labels"])[:, :, rows], lens[rows])
    return ref.label_scores(
        clf.params, HF, ids[rows], lens[rows], clf._label_ids,
        clf._label_lens, variant=variant, prefer=prefer,
        margin=tol["route_margin"])


@pytest.mark.parametrize("longest", [400, 60], ids=["compact-512", "padded"])
def test_prefill_and_label_passes_agree_with_the_full_forward(clf, longest):
    """The system's prompt prefill (the kernels on the compact token
    stream at a 512-wide step, on padded rows at a narrow one), its
    recurrent states and latent cache, and the three label continuations
    that fork them, against one plain forward a label over prompt + label
    tokens."""
    tol = ref.TEST_TOLERANCE
    lyrics = _lyrics(1, 12, longest) + [""]
    probe = [0, 2, 3, 5, 7, 8, 11, 12]
    ids, lens, scores, stats, labels = _system(clf, lyrics, probe)
    capacity = compact_capacity(int(lens.sum()), *ids.shape)
    assert llama.runs_compact(clf.config, ids.shape, capacity) == (
        longest == 400)
    judged = _judged(clf, ids, lens, stats)
    diff = np.abs(scores - judged["scores"])
    routing = judged["routing"]
    assert routing["wrong"] <= tol["wrong_choices"], routing
    assert np.median(diff) <= tol["label_score_median"]
    assert diff.max() <= tol["label_score_max"]
    kept = {k: v[:, probe] for k, v in judged["kept"].items()}
    held = ref.compare_kept(kept, stats["probe"], lens[probe])
    assert held["state_median"] <= tol["state_median"], held
    assert held["state_max"] <= tol["state_max"], held
    assert held["latents_median"] <= tol["latents_median"], held
    assert held["rope_keys_median"] <= tol["rope_keys_median"], held
    assert labels[-1] == "Neutral"                 # the empty lyric


def test_the_reference_in_int8_fails_the_tolerance(clf):
    tol = ref.TEST_TOLERANCE
    ids, lens, scores, stats, _ = _system(clf, LYRICS, np.arange(8))
    judged = _judged(clf, ids, lens, stats, variant="int8")
    diff = np.abs(scores - judged["scores"])
    assert judged["routing"]["wrong"] > 10 * tol["wrong_choices"]
    assert np.median(diff) > tol["label_score_median"]
    held = ref.compare_kept(
        {k: v[:, :8] for k, v in judged["kept"].items()}, stats["probe"],
        lens[:8])
    assert held["state_median"] > tol["state_median"]
    assert held["latents_median"] > tol["latents_median"]


def test_compact_stream_equals_the_padded_prefill_on_every_real_position(clf):
    """The forward that keeps its hidden state on the compact token set
    against the same call with lengths alone.  The first layer's state
    differs by the kernel's rounding alone (rows start elsewhere inside a
    block, so their chunks differ); behind the first routed layer some
    tokens' near-ties fall the other way (a few in a hundred at sixteen
    experts of width 32), so the rest is held by medians and shares."""
    cfg = clf.config
    rows, width = 4, 512
    lens = jnp.asarray([300, 41, 256, 101], jnp.int32)
    ids = jax.random.randint(jax.random.key(0), (rows, width), 16,
                             cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(width), (rows, width))
    mask = causal_mask(width, width + 8, 0) & (
        jnp.arange(width + 8)[None, None, None, :]
        < lens[:, None, None, None])
    capacity = compact_capacity(int(lens.sum()), rows, width)
    assert capacity == 768 and llama.runs_compact(cfg, ids.shape, capacity)

    def forward(**kw):
        (logits, caches), sown = clf.model.apply(
            {"params": clf.params}, ids, positions, mask,
            init_caches(cfg, rows, width + 8), last_position=lens - 1,
            prefill_lengths=lens, row_lengths=lens,
            mutable=["intermediates"], **kw)
        return logits, caches, llama._sown_by_layer(sown, "chosen")

    want, want_caches, want_chosen = forward()
    got, caches, chosen = forward(prefill_capacity=capacity)
    scale = float(jnp.abs(want).max())
    assert float(jnp.median(jnp.abs(got - want))) < 0.02 * scale
    real = np.asarray(jnp.arange(width)[None, :] < lens[:, None])
    for a, b in zip(chosen, want_chosen):
        same = (np.sort(np.asarray(a), -1) == np.sort(np.asarray(b), -1)
                ).all(-1)
        assert same[real].mean() > 0.85
    first, want_first = caches[0], want_caches[0]
    assert np.array_equal(np.asarray(first.conv), np.asarray(want_first.conv))
    assert float(jnp.abs(first.state - want_first.state).max()) < (
        0.01 * float(jnp.abs(want_first.state).max()))
    for a, b in zip(caches[1:3], want_caches[1:3]):
        assert float(jnp.median(jnp.abs(a.state - b.state))) < (
            0.01 * float(jnp.abs(b.state).max()))
    err = jnp.abs(caches[3].latents.astype(jnp.float32)
                  - want_caches[3].latents.astype(jnp.float32))
    assert float(jnp.median(err[:, :width][real])) < 0.05


def test_generation_steps_the_recurrent_state(clf):
    """``generate_batch`` (prefill, then a token a step through every
    layer's state or cache in one scan) gives the tokens of the explicit
    step loop."""
    prompts = ["love rain night", "the sun never stays gone baby " * 6]
    batch = clf.generate_batch(prompts, max_new_tokens=6, early_exit=False)
    assert batch == clf.generate_batch(prompts, max_new_tokens=6)
    # the step loop prefills 1,024 padded slots by the XLA form, the scan
    # the prompt's own width by the kernel: bfloat16 apart, and a random
    # model's largest logit turns on less; the first tokens agree
    alone = clf.generate(prompts[0], max_new_tokens=6)
    assert batch[0].split()[:3] == alone.split()[:3]
    assert len(batch[0].split()) == len(alone.split()) == 6


def test_decode_runtimes_refuse_the_recurrent_state(clf):
    from music_analyst_tpu.serving.decode_runtime import (
        decode_runtime_refusal,
        paged_runtime,
        slot_runtime,
    )

    assert "recurrent state" in decode_runtime_refusal(clf, "paged")
    assert "slot" in decode_runtime_refusal(clf, "slot")
    for build in (slot_runtime, paged_runtime):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            build(clf)


def test_cli_writes_the_jobs_files_and_counts_what_a_step_did(tmp_path):
    from music_analyst_tpu.cli.main import main

    fixture = os.path.join(REPO, "tests", "fixtures", "mini_songs.csv")
    assert main(["sentiment", fixture, "--model", "ling-tiny",
                 "--batch-size", "4", "--output-dir", str(tmp_path)]) == 0
    with open(tmp_path / "sentiment_totals.json", encoding="utf-8") as fh:
        assert sum(json.load(fh).values()) == 8
    with open(tmp_path / "run_manifest.json", encoding="utf-8") as fh:
        manifest = json.load(fh)
    counters, gauges = manifest["counters"], manifest["gauges"]
    assert counters["kda.tokens"] == 3 * (
        counters["decoder.tokens_real"] - 8 * 3)   # 3 KDA layers, 2 steps
    # 8 rows x 3 labels x the one position a label runs x 3 KDA layers
    assert counters["kda.state_steps"] == 8 * 3 * 1 * 3
    assert 0 < counters["moe.assignments_held"] < counters["moe.assignments"]
    assert counters["moe.assignments"] % (3 * 4) == 0
    assert gauges["recurrent_state_bytes"] == 4 * 3 * 4 * 16 * (64 + 18)
    assert gauges["latent_cache_bytes"] > 0
    record = next(r for r in manifest["profiling"]["compiles"]
                  if r["name"] == "llama_score_labels")
    assert record["attention_paths"]["kda_chunked"] == 3
    assert record["attention_paths"]["kda_recurrent"] == 3
    assert record["traced_paths"]["moe.group_limited"] == 6
    assert record["traced_paths"]["moe.experts_held"] == 6
    assert record["traced_paths"]["mla.gated"] == 2
    with open(tmp_path / "telemetry.jsonl", encoding="utf-8") as fh:
        spans = [json.loads(line) for line in fh]
    step = next(s["attrs"] for s in spans if s.get("name") == "compute")
    assert (step["kda_layers"], step["mla_layers"]) == (3, 1)
    assert step["assignments"] == step["tokens_real"] * 3 * 4
    assert 0 < step["assignments_held"] < step["assignments"]
    assert step["state_bytes"] == gauges["recurrent_state_bytes"]
    assert len(step["expert_load_max_over_mean"]) == 3
