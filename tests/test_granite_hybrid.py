"""The ``granitemoehybrid`` decoder kinds (Mamba-2 state-space layers on a
recurrent state, nine to one grouped-query layer without positions,
softmax-routed experts of which this chip holds a share, a shared expert,
tied embeddings, Granite's four multipliers) against the plain float32
reference ``perfbench/reference/granite_hybrid_f32.py``, at the
``granite-tiny`` size with seeded weights.

Layer tests run the program's modules in float32 on the reference's own
inputs (the XLA forms: the kernel's MXU operands are bfloat16 whatever the
model's dtype), so they hold the equations.  The three forms of the
recurrence are held to each other at the decays the assumed initialisation
gives and at ``delta A = -8`` a step.  The reference itself is held to the
modelling code ``transformers`` ships.  The end-to-end tests run the system
as it is served, bfloat16, the kernels under the interpreter, and hold it to
``TEST_TOLERANCE``.
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

from reference import granite_hybrid_f32 as ref  # noqa: E402

from music_analyst_tpu.models import llama  # noqa: E402
from music_analyst_tpu.models.layers import (  # noqa: E402
    MultiHeadAttention,
    causal_mask,
)
from music_analyst_tpu.models.llama import (  # noqa: E402
    PRESETS,
    LlamaConfig,
    init_caches,
)
from music_analyst_tpu.models.mamba2 import Mamba2Mixer, SSMState  # noqa: E402
from music_analyst_tpu.models.compact_stream import (  # noqa: E402
    RealPositions,
    compact_capacity,
)
from music_analyst_tpu.models.moe import (  # noqa: E402
    RoutedMoE,
    route_softmax_topk,
)
from music_analyst_tpu.ops import ssd_scan  # noqa: E402
from music_analyst_tpu.ops.kv_cache import KVCache  # noqa: E402

F32_TOL = 2e-4  # float32 program against float32 reference


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


HF = _as_reference_config(_preset("granite-tiny"))

_WORDS = ("love rain night baby tears dance road fire cold heart sun blue "
          "you me the and never always gone stay").split()


def _lyrics(seed: int, rows: int, longest: int = 400):
    rng = np.random.default_rng(seed)
    return [" ".join(rng.choice(_WORDS, size=int(n)))
            for n in rng.integers(5, longest, size=rows)]


LYRICS = _lyrics(0, 12) + [""]


@pytest.fixture(scope="module")
def clf():
    from music_analyst_tpu.engines.sentiment import get_backend

    return get_backend("granite-tiny")


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _hidden(rows, n_tok, dim, seed=0):
    return jax.random.normal(jax.random.key(seed), (rows, n_tok, dim),
                             jnp.float32)


def _mamba(cfg: LlamaConfig, **kw):
    return Mamba2Mixer(
        n_heads=cfg.mamba_n_heads, head_dim=cfg.mamba_head_dim,
        d_state=cfg.mamba_d_state, conv_kernel=cfg.mamba_conv_kernel,
        norm_eps=cfg.rms_norm_eps, dtype=jnp.float32, **kw)


# ----------------------------------------------------------- configuration

def test_presets_are_built_from_their_files(clf):
    cfg = clf.config
    assert [cfg.mixer(i) for i in range(cfg.n_layers)] == [
        "mamba", "mamba", "gqa", "mamba"]
    assert (cfg.ssm_layers, cfg.kda_layers, cfg.recurrent_state) == (
        3, 0, True)
    assert (cfg.n_experts, cfg.experts_held, cfg.moe_top_k,
            cfg.n_shared_experts, cfg.moe_router) == (
                8, (0, 4), 4, 2, "softmax_topk")
    assert (cfg.use_rope, cfg.attention_scale, cfg.tie_embeddings) == (
        False, 0.0625, True)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12.0, 0.22, 16.0)
    assert "lm_head" not in clf.params
    moe = clf.params["layer_0"]["feed_forward_moe"]
    assert moe["gate_experts"].shape == (4, 64, 32)
    assert moe["router"].shape == (64, 8)
    assert moe["shared_experts"]["gate_proj"]["kernel"].shape == (64, 64)
    full = PRESETS["granite-4.0-h-small"]()
    assert [full.mixer(i) for i in range(full.n_layers)] == (
        ["mamba"] * 5 + ["gqa"] + ["mamba"] * 4)
    assert (full.dim, full.mamba_n_heads, full.mamba_head_dim,
            full.mamba_d_state, full.n_heads, full.n_kv_heads,
            full.attn_head_dim) == (4096, 128, 64, 128, 32, 8, 128)
    assert (full.n_experts, full.experts_held, full.moe_top_k,
            full.moe_hidden_dim, full.n_shared_experts,
            full.vocab_size) == (72, (0, 36), 10, 768, 2, 50176)
    assert full.attention_scale == 0.0078125 and full.attn_impl == "flash"


def test_published_keys_are_the_catalogs_but_for_the_cut():
    """The full preset and the benchmark's configuration file carry the
    catalog row's keys unchanged but for the ones the cut lists."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog, encoding="utf-8") as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "granite-4.0-h-small")
    with open(os.path.join(REPO, "perfbench", "configs",
                           "granite-4.0-h-small.json"),
              encoding="utf-8") as fh:
        bench = json.load(fh)
    preset = _preset("granite-4.0-h-small")
    cut = {"num_hidden_layers", "layer_types", "num_local_experts",
           "vocab_size"}
    for key, value in row["config"].items():
        if key not in cut:
            assert bench[key] == value and preset[key] == value, key
    assert bench["source"] == row["source_url"]
    assert bench["layer_types"] == row["config"]["layer_types"][:10]
    assert (bench["num_local_experts"], preset["num_local_experts"]) == (
        36, 72)                      # held here | the router's width
    for key in cut:
        assert bench["published"][key] == row["config"][key]
    counted = bench["deployment"]["parameters"]
    params = jax.eval_shape(lambda: llama.init_params_by_layer(
        PRESETS["granite-4.0-h-small"]()))
    assert counted["total"] == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    assert bench["deployment"]["bytes_bfloat16"] == 2 * counted["total"]


@pytest.mark.parametrize("key,value", [
    ("position_embedding_type", "rope"), ("attention_bias", True),
    ("mamba_proj_bias", True), ("mamba_conv_bias", False),
    ("mamba_n_groups", 8), ("rope_scaling", {"type": "yarn"}),
    ("hidden_act", "gelu"), ("normalization_function", "layernorm"),
    ("mamba_expand", 4), ("shared_intermediate_size", 48),
    ("num_local_experts", 0),
])
def test_from_hf_config_refuses_by_name_what_it_cannot_run(key, value):
    hf = {**_preset("granite-tiny"), key: value}
    with pytest.raises(ValueError, match=key):
        LlamaConfig.from_hf_config(hf, **hf["runtime"])


def test_a_list_of_layer_kinds_and_a_period_exclude_each_other():
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(PRESETS["ling-tiny"](),
                            layer_types=("mamba",) * 4)
    with pytest.raises(ValueError, match="n_layers"):
        dataclasses.replace(PRESETS["granite-tiny"](),
                            layer_types=("mamba", "attention"))
    with pytest.raises(ValueError, match="mamba widths"):
        dataclasses.replace(PRESETS["granite-tiny"](), mamba_d_state=0)


def test_caches_are_of_each_layers_kind(clf):
    caches = init_caches(clf.config, 3, 40)
    assert [type(c) for c in caches] == [SSMState, SSMState, KVCache,
                                         SSMState]
    assert caches[0].state.shape == (3, 8, 16, 32)
    assert caches[0].state.dtype == jnp.float32
    assert caches[0].conv.shape == (3, 3, 8 * 16 + 2 * 32)
    assert caches[2].keys.shape == (3, 40, 2, 16)
    assert caches[0].with_length(7) is caches[0]


def test_init_draws_the_step_and_the_decay_inside_their_ranges(clf):
    p = clf.params["layer_0"]["attention"]
    delta = jax.nn.softplus(p["dt_bias"])
    assert 1e-3 <= float(delta.min()) and float(delta.max()) <= 1e-1
    rate = jnp.exp(p["A_log"])
    assert 1.0 <= float(rate.min()) and float(rate.max()) <= 16.0
    assert np.array_equal(np.asarray(p["D"]), np.ones(8, np.float32))
    assert p["A_log"].dtype == p["dt_bias"].dtype == jnp.float32
    assert float(jnp.abs(p["conv"].astype(jnp.float32)).max()) <= 0.5


# ------------------------------------------------- the recurrence's forms

def _operands(seed, rows, n_tok, heads, dim, n_state, fast=False):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (rows, n_tok, heads, dim))
    b = jax.random.normal(ks[1], (rows, n_tok, n_state))
    c = jax.random.normal(ks[2], (rows, n_tok, n_state))
    if fast:      # delta A = -8 a step: a state forgets within a token
        dt = jnp.full((rows, n_tok, heads), 0.5)
        a = jnp.full((heads,), -16.0)
    else:         # what the assumed initialisation gives
        dt = jnp.exp(jax.random.uniform(
            ks[3], (rows, n_tok, heads), minval=np.log(1e-3),
            maxval=np.log(1e-1)))
        a = -jax.random.uniform(ks[4], (heads,), minval=1.0, maxval=16.0)
    return x, dt, a, b, c


_CASES = {"init-decays": False, "fast-decay": True}


@pytest.mark.parametrize("case", list(_CASES))
def test_chunked_xla_form_is_the_recurrence(case):
    rows, n_tok, heads, dim, n_state = 3, 256, 4, 16, 32
    ops = _operands(1, rows, n_tok, heads, dim, n_state, _CASES[case])
    start = jax.random.normal(jax.random.key(9),
                              (rows, heads, dim, n_state))
    valid = jnp.arange(n_tok)[None, :] < jnp.asarray([256, 130, 7])[:, None]
    want_y, want_s = ssd_scan.ssd_recurrent(*ops, start, valid)
    got_y, got_s = ssd_scan.ssd_chunked_xla(*ops, start, valid)
    scale = float(jnp.abs(want_y).max())
    real = valid[..., None, None]
    assert float(jnp.abs(jnp.where(real, got_y - want_y, 0)).max()) < (
        1e-5 * scale)
    assert float(jnp.abs(got_s - want_s).max()) < 1e-5 * max(
        1.0, float(jnp.abs(want_s).max()))
    # a row of 7 tokens leaves the state of its 249 missing ones alone
    alone, _ = ssd_scan.ssd_recurrent(
        *(v[2:, :7] if v.ndim > 1 else v for v in ops), start[2:])
    assert np.allclose(np.asarray(want_y[2, :7]), np.asarray(alone[0]),
                       atol=1e-6)


@pytest.mark.parametrize("case", list(_CASES))
@pytest.mark.parametrize("form", ["padded", "compact"])
def test_kernel_is_the_recurrence_on_bfloat16_operands(case, form):
    """The Pallas kernel (under the interpreter) on padded rows and on the
    compact stream, against the token-by-token recurrence in float32 on the
    same bfloat16 operands."""
    rows, n_tok, heads, dim, n_state = 4, 256, 8, 16, 32
    lens = np.asarray([256, 41, 200, 3])
    x, dt, a, b, c = _operands(2, rows, n_tok, heads, dim, n_state,
                               _CASES[case])
    x, b, c = (v.astype(jnp.bfloat16) for v in (x, b, c))
    lens_d = jnp.asarray(lens, jnp.int32)
    valid = jnp.arange(n_tok)[None, :] < lens_d[:, None]
    zeros = jnp.zeros((rows, heads, dim, n_state), jnp.float32)
    want_y, want_s = ssd_scan.ssd_recurrent(x, dt, a, b, c, zeros, valid)
    flat_x = x.reshape(rows, n_tok, heads * dim)
    if form == "padded":
        starts = jnp.arange(rows, dtype=jnp.int32) * n_tok

        def flat(v):
            return v.reshape(rows * n_tok, -1)

        got_y, got_s = ssd_scan.ssd_chunked(
            flat(flat_x), flat(dt), a, flat(b), flat(c), starts,
            starts + lens_d, valid.reshape(-1), heads, n_tok)
        got_y = got_y.reshape(rows, n_tok, heads, dim)
    else:
        capacity = compact_capacity(int(lens.sum()), rows, n_tok)
        assert capacity == 512
        packed = RealPositions.of(lens_d, n_tok, capacity)
        got_y, got_s = ssd_scan.ssd_chunked(
            packed.gather(flat_x), packed.gather(dt), a, packed.gather(b),
            packed.gather(c), packed.start, packed.start + lens_d,
            packed.valid, heads, n_tok)
        assert bool(jnp.isfinite(got_y.astype(jnp.float32)).all())
        got_y = packed.put_back(got_y).reshape(rows, n_tok, heads, dim)
    real = valid[..., None, None]
    y_scale = float(jnp.abs(want_y).max())
    err = jnp.abs(jnp.where(real, got_y.astype(jnp.float32) - want_y, 0))
    assert float(err.max()) < 0.02 * y_scale
    assert float(jnp.abs(got_s - want_s).max()) < 0.02 * float(
        jnp.abs(want_s).max())


def test_kernel_rows_do_not_see_their_neighbours_or_the_fillers():
    """A row's outputs and final state on the compact stream are the same
    whatever lies before it, behind it, or in the filler slots."""
    heads, dim, n_state, n_tok = 8, 16, 32, 256
    x, dt, a, b, c = _operands(3, 5, n_tok, heads, dim, n_state)
    x = x.reshape(5, n_tok, heads * dim).astype(jnp.bfloat16)
    b, c = b.astype(jnp.bfloat16), c.astype(jnp.bfloat16)

    def run(rows, lens, capacity):
        rows = jnp.asarray(rows)
        lens_d = jnp.asarray(lens, jnp.int32)
        packed = RealPositions.of(lens_d, n_tok, capacity)
        y, s = ssd_scan.ssd_chunked(
            packed.gather(x[rows]), packed.gather(dt[rows]), a,
            packed.gather(b[rows]), packed.gather(c[rows]), packed.start,
            packed.start + lens_d, packed.valid, heads, n_tok)
        return packed.put_back(y.astype(jnp.float32)), s

    y_all, s_all = run([0, 1, 2, 3, 4], [100, 37, 3, 200, 90], 512)
    # row 1 alone at the stream's head; rows 2 and 3 behind another row
    y_one, s_one = run([1], [37], 128)
    y_two, s_two = run([4, 2, 3], [11, 3, 200], 256)
    for got, got_s, row, at, n in ((y_one, s_one, 1, 0, 37),
                                   (y_two, s_two, 2, 1, 3),
                                   (y_two, s_two, 3, 2, 200)):
        # the same arithmetic on other chunk boundaries: bfloat16 apart
        scale = float(jnp.abs(y_all[row, :n]).max())
        assert float(jnp.abs(got[at, :n] - y_all[row, :n]).max()) < (
            0.02 * scale)
        assert float(jnp.abs(got_s[at] - s_all[row]).max()) < 0.02 * float(
            jnp.abs(s_all[row]).max())


def test_convolution_and_state_restart_at_a_rows_first_slot(clf):
    """The Mamba-2 layer on the compact stream against the same rows
    padded: tails equal, states and outputs a kernel's rounding apart."""
    cfg = clf.config
    rows, width = 4, 512
    lens = jnp.asarray([300, 41, 256, 101], jnp.int32)
    capacity = compact_capacity(int(lens.sum()), rows, width)
    packed = RealPositions.of(lens, width, capacity)
    h = _hidden(rows, width, cfg.dim, 4)
    mixer = _mamba(cfg)
    state = SSMState.zeros(rows, 8, 16, 32, dtype=jnp.float32)
    params = mixer.init(jax.random.key(0), h)
    positions = jnp.broadcast_to(jnp.arange(width), (rows, width))
    want, want_state = mixer.apply(params, h, positions, state, lens)
    got, got_state = mixer.apply(
        params, packed.gather(h)[None], packed.gather(positions)[None],
        state, lens, None, packed)
    got = packed.put_back(got[0])
    assert np.allclose(np.asarray(got_state.conv),
                       np.asarray(want_state.conv), atol=1e-6)
    assert float(jnp.abs(got_state.state - want_state.state).max()) < (
        0.01 * float(jnp.abs(want_state.state).max()))
    err = jnp.abs(jnp.where(packed.real[..., None], got - want, 0))
    assert float(err.max()) < 0.02 * float(jnp.abs(want).max())


# ------------------------------------------------ layers against reference

@pytest.mark.parametrize("n_tok", [256, 24], ids=["chunked-xla", "recurrent"])
def test_mamba_layer_matches_reference(clf, n_tok):
    """The layer in float32 (no declared lengths: the XLA forms) against
    the reference's token-by-token layer on the same weights, output, final
    state and convolution tail; then a continuation from that state against
    the reference over the longer sequence."""
    cfg = clf.config
    p = _f32(clf.params["layer_0"]["attention"])
    h = _hidden(2, n_tok + 8, cfg.dim, n_tok)
    mixer = _mamba(cfg)
    state = SSMState.zeros(2, 8, 16, 32, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, new = mixer.apply({"params": p}, h[:, :n_tok], None, state)
        more, newer = mixer.apply({"params": p}, h[:, n_tok:], None, new)
        want, want_state, want_tail = ref.mamba_mixer(
            p, h, HF, snapshot_at=jnp.full((2,), n_tok - 1))
        _, end_state, end_tail = ref.mamba_mixer(p, h, HF)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want[:, :n_tok]).max()) < F32_TOL * scale
    assert float(jnp.abs(more - want[:, n_tok:]).max()) < F32_TOL * scale
    for a, b in ((new.state, want_state), (new.conv, want_tail),
                 (newer.state, end_state), (newer.conv, end_tail)):
        assert float(jnp.abs(a - b).max()) < F32_TOL * max(
            1.0, float(jnp.abs(b).max()))


def test_attention_without_positions_under_the_published_scale(clf):
    """The grouped-query layer in float32: no rotary, scores times
    ``attention_multiplier``, against the reference; through the cache's
    causal view (the masked XLA form at this width) and through the mask."""
    from music_analyst_tpu.ops.kv_cache import BlockCausalPrefill

    cfg = clf.config
    p = _f32(clf.params["layer_2"]["attention"])
    n_tok = 48
    h = _hidden(2, n_tok, cfg.dim, 5)
    attn = MultiHeadAttention(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.attn_head_dim, use_rope=cfg.use_rope,
        dtype=jnp.float32, scale=cfg.attention_scale)
    lens = jnp.asarray([48, 20], jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, keys, values = ref.attention(p, h, HF)
        masked = attn.apply({"params": p}, h, causal_mask(n_tok, n_tok, 0))
        cache = KVCache.zeros(2, n_tok + 8, cfg.n_kv_heads,
                              cfg.attn_head_dim, jnp.float32)
        viewed, view = attn.apply(
            {"params": p}, h, None, None,
            BlockCausalPrefill(cache, lens, 1, scale=cfg.attention_scale))
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(masked - want).max()) < F32_TOL * scale
    real = (jnp.arange(n_tok)[None, :] < lens[:, None])[..., None]
    assert float(jnp.abs(jnp.where(real, viewed - want, 0)).max()) < (
        F32_TOL * scale)
    assert np.array_equal(np.asarray(view.cache.length), [48, 20])
    assert float(jnp.abs(view.cache.keys[:, :n_tok] - keys).max()) < 1e-5
    assert float(jnp.abs(view.cache.values[:, :n_tok] - values).max()) < 1e-5
    # the scale is the published one, not head_dim ** -0.5
    plain = dataclasses.replace(attn, scale=None).apply(
        {"params": p}, h, causal_mask(n_tok, n_tok, 0))
    assert float(jnp.abs(plain - want).max()) > 10 * F32_TOL * scale


def test_a_scale_without_an_attention_that_takes_it_is_refused():
    attn = MultiHeadAttention(n_heads=2, dtype=jnp.float32, scale=0.1)
    x = _hidden(1, 8, 16)
    with pytest.raises(ValueError, match="softmax scale"):
        attn.init(jax.random.key(0), x, lengths=jnp.asarray([8]))


def _moe(cfg: LlamaConfig, held, dtype=jnp.float32):
    return RoutedMoE(
        cfg.n_experts, cfg.moe_hidden_dim, cfg.moe_top_k,
        n_shared=cfg.n_shared_experts, norm_topk_prob=cfg.norm_topk_prob,
        dtype=dtype, router=cfg.moe_router, experts_held=held)


def test_softmax_router_meets_a_share_and_a_shared_expert(clf):
    """``RoutedMoE(router="softmax_topk", experts_held=...)`` with the
    shared SwiGLU, in float32, against the reference's share of the layer:
    the two have not met before this configuration."""
    cfg = clf.config
    p = _f32(clf.params["layer_1"]["feed_forward_moe"])
    h = _hidden(2, 40, cfg.dim, 6)
    with jax.default_matmul_precision("highest"):
        got, sown = _moe(cfg, (0, 4)).apply(
            {"params": p}, h, mutable=["intermediates"])
        want, chosen, _ = ref.moe_ffn(p, h, HF, (0, 4))
    assert float(jnp.abs(got - want).max()) < F32_TOL * float(
        jnp.abs(want).max())
    mine = np.sort(np.asarray(
        sown["intermediates"]["chosen"][0]), -1)
    assert np.array_equal(mine, np.sort(np.asarray(chosen), -1))
    assert int(mine.max()) > 3              # absent experts are chosen too


def test_two_shares_add_up_to_the_uncut_layer(clf):
    """The routed parts that the two shares of 4 / 8 experts give, with the
    shared expert counted once, add up to the uncut reference layer, in the
    reference and in the program."""
    cfg = clf.config
    held = _f32(clf.params["layer_1"]["feed_forward_moe"])
    rest = jax.tree_util.tree_map(
        lambda a: jax.random.normal(jax.random.key(7), a.shape) * 0.1,
        {k: held[k] for k in ("gate_experts", "up_experts", "down_experts")})
    whole = {**held, **{k: jnp.concatenate([held[k], rest[k]])
                        for k in rest}}
    second = {**held, **rest}
    h = _hidden(2, 32, cfg.dim, 8)
    with jax.default_matmul_precision("highest"):
        uncut, _, _ = ref.moe_ffn(whole, h, HF, (0, 8))
        parts = [ref.moe_ffn(p, h, HF, share, shared=False)[0]
                 for p, share in ((held, (0, 4)), (second, (4, 4)))]
        shared_once = ref.swiglu(held["shared_experts"], h)
        program = [_moe(cfg, share).apply({"params": p}, h)
                   for p, share in ((held, (0, 4)), (second, (4, 4)))]
    scale = float(jnp.abs(uncut).max())
    assert float(jnp.abs(sum(parts) + shared_once - uncut).max()) < (
        F32_TOL * scale)
    # each share of the program adds the shared expert: once too many
    assert float(jnp.abs(sum(program) - shared_once - uncut).max()) < (
        F32_TOL * scale)
    assert float(jnp.abs(parts[0]).max()) > 0.05 * scale


# ------------------------------------------ reference against transformers

def _torch_granite(seed: int = 0):
    torch = pytest.importorskip("torch")
    modelling = pytest.importorskip(
        "transformers.models.granitemoehybrid.modeling_granitemoehybrid")
    from transformers.models.granitemoehybrid import GraniteMoeHybridConfig

    keys = {k: v for k, v in _preset("granite-tiny").items()
            if k not in ("_note", "runtime", "model_type")}
    config = GraniteMoeHybridConfig(**keys)
    torch.manual_seed(seed)
    model = modelling.GraniteMoeHybridForCausalLM(config).float().eval()
    with torch.no_grad():   # the assumed init's ranges, not _init_weights'
        model.model.embed_tokens.weight.normal_(0, 1.0)
        for layer in model.model.layers:
            if layer.mamba is not None:
                layer.mamba.A_log.copy_(torch.log(
                    torch.empty(8).uniform_(1.0, 16.0)))
                layer.mamba.dt_bias.copy_(torch.empty(8).uniform_(-4.0, -2.0))
                layer.mamba.D.copy_(torch.empty(8).uniform_(0.5, 1.5))
                layer.mamba.conv1d.bias.copy_(torch.empty(192).normal_() * 0.1)
            layer.block_sparse_moe.input_linear.weight.normal_(0, 0.1)
            layer.block_sparse_moe.output_linear.weight.normal_(0, 0.1)
            layer.block_sparse_moe.router.layer.weight.normal_(0, 0.5)
    return torch, modelling, model


def _params_from_torch(model) -> dict:
    """The modelling code's weights in this repository's parameter tree:
    the mapping a checkpoint loader would make."""
    def t(w):
        return jnp.asarray(w.detach().numpy())

    dim, heads, kv, d = 64, 4, 2, 16
    inner, n = 128, 32
    params = {
        "tok_embeddings": {"embedding": t(model.model.embed_tokens.weight)},
        "norm": {"scale": t(model.model.norm.weight)}}
    for i, layer in enumerate(model.model.layers):
        if layer.mamba is not None:
            m = layer.mamba
            fused = t(m.in_proj.weight).T                  # [D, 2I + 2N + H]
            mixer = {
                "in_proj": fused[:, :2 * inner + 2 * n],
                "dt_proj": fused[:, 2 * inner + 2 * n:],
                "conv": t(m.conv1d.weight)[:, 0, :].T,     # [K, I + 2N]
                "conv_bias": t(m.conv1d.bias), "A_log": t(m.A_log),
                "dt_bias": t(m.dt_bias), "D": t(m.D),
                "norm": t(m.norm.weight), "out_proj": t(m.out_proj.weight).T,
            }
        else:
            a = layer.self_attn
            mixer = {
                "q_proj": {"kernel": t(a.q_proj.weight).T.reshape(
                    dim, heads, d)},
                "k_proj": {"kernel": t(a.k_proj.weight).T.reshape(dim, kv, d)},
                "v_proj": {"kernel": t(a.v_proj.weight).T.reshape(dim, kv, d)},
                "o_proj": {"kernel": t(a.o_proj.weight).T.reshape(
                    heads, d, dim)},
            }
        moe = layer.block_sparse_moe
        fused = t(moe.input_linear.weight)                 # [E, 2W, D]
        width = fused.shape[1] // 2
        shared_in = t(layer.shared_mlp.input_linear.weight)   # [2S, D]
        s_width = shared_in.shape[0] // 2
        params[f"layer_{i}"] = {
            "attention": mixer,
            "attention_norm": {"scale": t(layer.input_layernorm.weight)},
            "ffn_norm": {"scale": t(layer.post_attention_layernorm.weight)},
            "feed_forward_moe": {
                "gate_experts": jnp.swapaxes(fused[:, :width], 1, 2),
                "up_experts": jnp.swapaxes(fused[:, width:], 1, 2),
                "down_experts": jnp.swapaxes(
                    t(moe.output_linear.weight), 1, 2),
                "router": t(moe.router.layer.weight).T,
                "shared_experts": {
                    "gate_proj": {"kernel": shared_in[:s_width].T},
                    "up_proj": {"kernel": shared_in[s_width:].T},
                    "down_proj": {"kernel": t(
                        layer.shared_mlp.output_linear.weight).T},
                },
            },
        }
    return params


def test_reference_is_transformers_granitemoehybrid():
    """A tiny ``GraniteMoeHybridForCausalLM`` with seeded weights copied
    into the reference's parameter tree: the logits of the uncut reference
    (all 8 experts) within float32 rounding of the modelling code's, and
    the same weights through the PROGRAM in float32."""
    torch, _, model = _torch_granite()
    params = _params_from_torch(model)
    ids = np.random.default_rng(0).integers(0, 4096, (2, 40))
    with torch.no_grad():
        want = model(torch.as_tensor(ids)).logits.numpy()
    uncut = {**HF, "model": {"experts_held": [0, 8]}}
    read_at = np.broadcast_to(np.arange(40), (2, 40))
    got = ref.forward(params, uncut, ids, read_at)["logits"]
    scale = float(np.abs(want).max())
    assert scale > 0.5                     # logits that say something
    assert float(np.abs(got - want).max()) < 2e-4 * scale
    cfg = dataclasses.replace(
        PRESETS["granite-tiny"](), dtype="float32", param_dtype="float32",
        experts_held=None, attn_impl="dense")
    with jax.default_matmul_precision("highest"):
        logits, _ = llama.LlamaModel(cfg).apply(
            {"params": params}, jnp.asarray(ids),
            jnp.broadcast_to(jnp.arange(40), (2, 40)),
            causal_mask(40, 40, 0))
    assert float(np.abs(np.asarray(logits) - want).max()) < 2e-4 * scale


def test_softmax_over_the_chosen_is_transformers_top_k_gating():
    """``route_softmax_topk`` with ``norm_topk_prob`` (softmax over all, the
    chosen renormalised) and the reference's router (softmax over the chosen
    logits) against ``GraniteMoeHybridTopKGating``."""
    torch, modelling, _ = _torch_granite()
    gate = modelling.GraniteMoeHybridTopKGating(64, 8, 4).float()
    torch.manual_seed(1)
    with torch.no_grad():
        gate.layer.weight.normal_(0, 0.5)
        x = torch.randn(50, 64)
        order, batch_index, gates, _, logits = gate(x)
    want = np.zeros((50, 8), np.float32)
    chosen_flat = logits.topk(4, dim=1)[1].flatten()[order]
    np.add.at(want, (batch_index.numpy(), chosen_flat.numpy()),
              gates.numpy())
    chosen, weights = route_softmax_topk(jnp.asarray(logits.numpy()), 4, True)
    mine = np.zeros((50, 8), np.float32)
    np.put_along_axis(mine, np.asarray(chosen), np.asarray(weights), axis=1)
    assert np.allclose(mine, want, atol=1e-6)
    p = {"router": jnp.asarray(gate.layer.weight.detach().numpy().T)}
    _, combine, _ = ref.route(p, jnp.asarray(x.numpy()), HF, (0, 8))
    assert np.allclose(np.asarray(combine), want, atol=1e-6)


# -------------------------------------------------------------- end to end

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
    """The system's prompt prefill (the kernels on the compact token stream
    at a 512-wide step, on padded rows at a narrow one), its recurrent
    states, convolution tails and key/value cache, and the three label
    continuations that fork them, against one plain forward a label over
    prompt + label tokens."""
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
    for name in ref.KEPT_LIMITS:
        assert held[name] <= tol[name], held
    assert labels[-1] == "Neutral"                 # the empty lyric


def test_the_reference_in_int8_fails_the_tolerance(clf):
    tol = ref.TEST_TOLERANCE
    ids, lens, scores, stats, _ = _system(clf, LYRICS, np.arange(8))
    judged = _judged(clf, ids, lens, stats, variant="int8")
    held = ref.compare_kept(
        {k: v[:, :8] for k, v in judged["kept"].items()}, stats["probe"],
        lens[:8])
    for name in ("state_median", "conv_median", "keys_median",
                 "values_median"):
        assert held[name] > tol[name], held


def test_single_token_steps_through_states_and_cache_are_the_full_forward(
        clf):
    """Prefill, then eight teacher-forced single-token steps through every
    layer's state or cache (``decode_step_program``'s call), logits against
    the reference's one full forward over prompt + the eight tokens."""
    cfg = clf.config
    rows, width, steps = 3, 64, 8
    lens = np.asarray([64, 17, 40])
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
    got = [np.asarray(logits[:, 0])]
    for t in range(steps):
        kv_pos = jnp.arange(total)[None, None, None, :]
        seen = (kv_pos < lens_d[:, None, None, None]) | (
            (kv_pos >= width) & (kv_pos - width <= t))
        logits, caches = clf.model.apply(
            {"params": clf.params}, jnp.asarray(forced[:, t:t + 1]),
            (lens_d + t)[:, None], seen, caches)
        got.append(np.asarray(logits[:, 0]))
    got = np.stack(got[:-1], axis=1)                       # [R, steps, V]
    sequences = np.zeros((rows, total), np.int32)
    for r, n in enumerate(lens):
        sequences[r, :n] = ids[r, :n]
        sequences[r, n:n + steps] = forced[r]
    read_at = (lens[:, None] - 1) + np.arange(steps)[None, :]
    want = ref.forward(clf.params, HF, sequences, read_at)["logits"]
    scale = float(np.abs(want).max())
    assert float(np.median(np.abs(got - want))) < 0.01 * scale
    assert float(np.abs(got - want).max()) < 0.08 * scale


def test_compact_stream_equals_the_padded_prefill_on_every_real_position(clf):
    """The forward that keeps its hidden state on the compact token set
    against the same call with lengths alone: the first layer's state and
    tail, the attention layer's keys, and the logits."""
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
        return clf.model.apply(
            {"params": clf.params}, ids, positions, mask,
            init_caches(cfg, rows, width + 8), last_position=lens - 1,
            prefill_lengths=lens, row_lengths=lens, **kw)

    want, want_caches = forward()
    got, caches = forward(prefill_capacity=capacity)
    scale = float(jnp.abs(want).max())
    assert float(jnp.median(jnp.abs(got - want))) < 0.02 * scale
    first, want_first = caches[0], want_caches[0]
    assert np.array_equal(np.asarray(first.conv), np.asarray(want_first.conv))
    assert float(jnp.abs(first.state - want_first.state).max()) < (
        0.01 * float(jnp.abs(want_first.state).max()))
    real = np.asarray(jnp.arange(width)[None, :] < lens[:, None])
    err = jnp.abs(caches[2].keys.astype(jnp.float32)
                  - want_caches[2].keys.astype(jnp.float32))
    assert float(jnp.median(err[:, :width][real])) < 0.05
    # behind a row's length the compact prefill leaves the cache's zeros
    assert float(jnp.abs(caches[2].keys[:, :width][~real]).max()) == 0.0


def test_multipliers_of_one_and_an_untied_head_are_the_program_it_was():
    """The three scalars at 1, no published scale and an untied head lower
    to the text the configuration lowers to without the fields; each of them
    set changes it."""
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
    same, _ = lowered(dataclasses.replace(
        base, embedding_multiplier=1.0, residual_multiplier=1.0,
        logits_scaling=1.0, attention_scale=0.0, use_rope=True,
        tie_embeddings=False))
    assert same == plain and "lm_head" in params
    for change in ({"embedding_multiplier": 12.0},
                   {"residual_multiplier": 0.22}, {"logits_scaling": 16.0},
                   {"attention_scale": 0.0078125}, {"use_rope": False}):
        assert lowered(dataclasses.replace(base, **change))[0] != plain
    tied, tied_params = lowered(dataclasses.replace(base, tie_embeddings=True))
    assert tied != plain and "lm_head" not in tied_params


def test_generation_steps_the_recurrent_state(clf):
    """``generate_batch`` (prefill, then a token a step through every
    layer's state or cache in one scan) gives the tokens of the explicit
    step loop."""
    prompts = ["love rain night", "the sun never stays gone baby " * 6]
    batch = clf.generate_batch(prompts, max_new_tokens=6, early_exit=False)
    assert batch == clf.generate_batch(prompts, max_new_tokens=6)
    alone = clf.generate(prompts[0], max_new_tokens=6)
    assert batch[0].split()[:2] == alone.split()[:2]
    assert len(batch[0].split()) == len(alone.split()) == 6


def test_decode_runtimes_refuse_the_recurrent_state_not_a_latent_cache(clf):
    from music_analyst_tpu.serving.decode_runtime import (
        decode_runtime_refusal,
        paged_runtime,
        slot_runtime,
    )

    refusal = decode_runtime_refusal(clf, "paged")
    assert "recurrent state" in refusal and "SSMState" in refusal
    assert "latent" not in refusal
    for build in (slot_runtime, paged_runtime):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            build(clf)


def test_cli_writes_the_jobs_files_and_counts_what_a_step_did(tmp_path):
    from music_analyst_tpu.cli.main import main

    fixture = os.path.join(REPO, "tests", "fixtures", "mini_songs.csv")
    assert main(["sentiment", fixture, "--model", "granite-tiny",
                 "--batch-size", "4", "--output-dir", str(tmp_path)]) == 0
    with open(tmp_path / "sentiment_totals.json", encoding="utf-8") as fh:
        assert sum(json.load(fh).values()) == 8
    with open(tmp_path / "run_manifest.json", encoding="utf-8") as fh:
        manifest = json.load(fh)
    counters, gauges = manifest["counters"], manifest["gauges"]
    assert counters["ssm.tokens"] == 3 * (
        counters["decoder.tokens_real"] - 8 * 3)   # 3 Mamba layers, 2 steps
    # 8 rows x 3 labels x the one position a label runs x 3 Mamba layers
    assert counters["ssm.state_steps"] == 8 * 3 * 1 * 3
    assert 0 < counters["moe.assignments_held"] < counters["moe.assignments"]
    assert counters["traced.embeddings.tied"] > 0
    assert counters["traced.moe.experts_held"] > 0
    # 4 rows x 3 layers x (8 x 16 x 32 float32 + 3 x 192 bfloat16)
    assert gauges["recurrent_state_bytes"] == 4 * 3 * (16384 + 1152)
    assert gauges["kv_cache_bytes"] > 0
    assert "kda.tokens" not in counters and "latent_cache_bytes" not in gauges
