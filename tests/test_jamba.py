"""The ``jamba`` decoder kinds (Mamba-1 selective-scan layers on a state a
channel, a low-rank step size, Jamba's norms on the step's input, ``B`` and
``C``, around grouped-query attention with one key/value head and no
positions, a dense SwiGLU in every layer, tied embeddings) against the plain
float32 reference ``perfbench/reference/jamba_f32.py``, at the
``jamba-tiny`` size with seeded weights.

Layer tests run the program's modules in float32 on the reference's own
inputs, so they hold the equations; the kernel is held to the token-by-token
form across chunk boundaries and row starts.  The reference itself is held to
the modelling code ``transformers`` ships.  The end-to-end tests run the
system as it is served, bfloat16, the kernels under the interpreter, and hold
it to ``TEST_TOLERANCE``, which the reference in int8 breaks, and the system
with its state carried in bfloat16 breaks by its ``scan_state_max``.
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

from reference import jamba_f32 as ref  # noqa: E402

from music_analyst_tpu.models import llama  # noqa: E402
from music_analyst_tpu.models.compact_stream import (  # noqa: E402
    RealPositions,
    compact_capacity,
)
from music_analyst_tpu.models.layers import causal_mask  # noqa: E402
from music_analyst_tpu.models.llama import (  # noqa: E402
    PRESETS,
    LlamaConfig,
    init_caches,
)
from music_analyst_tpu.models.mamba1 import (  # noqa: E402
    Mamba1Mixer,
    SelectiveState,
)
from music_analyst_tpu.ops import selective_scan as scan  # noqa: E402

F32_TOL = 2e-4  # float32 program against float32 reference


def _preset(name):
    path = os.path.join(REPO, "music_analyst_tpu", "models", "presets",
                        name + ".json")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


HF = _preset("jamba-tiny")

_WORDS = ("love rain night baby tears dance road fire cold heart sun blue "
          "you me the and never always gone stay").split()


def _lyrics(seed: int, rows: int, longest: int = 400):
    rng = np.random.default_rng(seed)
    return [" ".join(rng.choice(_WORDS, size=int(n)))
            for n in rng.integers(5, longest, size=rows)]


@pytest.fixture(scope="module")
def clf():
    from music_analyst_tpu.engines.sentiment import get_backend

    return get_backend("jamba-tiny")


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _mixer(cfg: LlamaConfig, dtype=jnp.float32):
    return Mamba1Mixer(
        inner=cfg.mamba_inner_dim, d_state=cfg.mamba_d_state,
        dt_rank=cfg.mamba_dt_rank, conv_kernel=cfg.mamba_conv_kernel,
        norm_eps=cfg.rms_norm_eps, dtype=dtype)


# ----------------------------------------------------------- configuration

def test_presets_are_built_from_their_files(clf):
    cfg = clf.config
    assert [cfg.mixer(i) for i in range(cfg.n_layers)] == [
        "mamba1", "mamba1", "gqa", "mamba1"]
    assert (cfg.selective_layers, cfg.ssm_layers, cfg.recurrent_state) == (
        3, 0, True)
    assert (cfg.mamba_inner_dim, cfg.mamba_d_state, cfg.mamba_dt_rank,
            cfg.n_kv_heads, cfg.n_experts) == (128, 16, 8, 1, 0)
    assert (cfg.use_rope, cfg.attention_scale, cfg.tie_embeddings) == (
        False, 0.0, True)
    assert "lm_head" not in clf.params
    mixer = clf.params["layer_0"]["attention"]
    assert mixer["A_log"].shape == (128, 16)
    assert mixer["A_log"].dtype == mixer["dt_bias"].dtype == jnp.float32
    assert mixer["x_proj"].shape == (128, 8 + 2 * 16)
    assert mixer["dt_proj"].shape == (8, 128)
    # a dense SwiGLU in the attention layer too, in the parameters' dtype
    assert clf.params["layer_2"]["feed_forward"]["up_proj"][
        "kernel"].dtype == jnp.bfloat16


def _bench_config():
    with open(os.path.join(REPO, "perfbench", "configs",
                           "ai21-jamba2-3b.json"), encoding="utf-8") as fh:
        return json.load(fh)


def test_the_published_config_gives_two_attention_layers_and_26_mamba1():
    """``from_hf_config`` on the source's ``config.json`` as the benchmark's
    file records it (``published``): layers 7 and 21 attention (offset 7 of
    period 14), 26 Mamba-1, a dense feed-forward everywhere, one key/value
    head; the preset and the file carry every published key unchanged."""
    bench = _bench_config()
    published = {k: v for k, v in bench["published"].items()
                 if k not in ("corpus_songs", "note")}
    cfg = LlamaConfig.from_hf_config(published)
    assert [i for i in range(28) if cfg.mixer(i) == "gqa"] == [7, 21]
    assert cfg.selective_layers == 26 and cfg.n_kv_heads == 1
    assert (cfg.n_experts, cfg.hidden_dim) == (0, 8192)
    assert not any(cfg.routed_layer(i) for i in range(28))
    assert (cfg.mamba_inner_dim, cfg.mamba_dt_rank, cfg.attn_head_dim) == (
        5120, 160, 128)
    preset = _preset("ai21-jamba2-3b")
    for key, value in published.items():
        assert bench[key] == value and preset[key] == value, key
    assert bench["model"]["layer_types"] == list(cfg.layer_types)


def test_parameters_at_the_published_widths_are_counted():
    """The whole model, measured by shape alone (no allocation), against
    the count the configuration file states."""
    params = jax.eval_shape(lambda: llama.init_params_by_layer(
        PRESETS["ai21-jamba2-3b"]()))
    total = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert total == 3_029_337_472
    counted = _bench_config()["deployment"]["parameters"]
    assert counted["total"] == total
    assert sum(a.size for a in jax.tree_util.tree_leaves(
        params["layer_0"]["attention"])) == counted["mamba1_mixer"]


@pytest.mark.parametrize("key,value", [
    ("num_experts", 16), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("hidden_act", "gelu"),
    ("sliding_window", 4096), ("attn_layer_offset", 14),
])
def test_from_hf_config_refuses_by_name_what_it_cannot_run(key, value):
    with pytest.raises(ValueError, match=key):
        LlamaConfig.from_hf_config({**HF, key: value})


def test_a_step_rank_of_auto_is_the_sources_rule():
    cfg = LlamaConfig.from_hf_config({**HF, "mamba_dt_rank": "auto"})
    assert cfg.mamba_dt_rank == 4                       # ceil(64 / 16)


def test_caches_are_of_each_layers_kind(clf):
    caches = init_caches(clf.config, 2, 24)
    assert [type(c).__name__ for c in caches] == [
        "SelectiveState", "SelectiveState", "KVCache", "SelectiveState"]
    assert caches[0].state.shape == (2, 128, 16)
    assert caches[0].state.dtype == jnp.float32
    assert caches[0].conv.shape == (2, 3, 128)


# ------------------------------------------------------------------ kernel

def _operands(seed, lens, inner=256, n=16, rank=8, fillers=64):
    """A stream of rows ``lens`` and non-zero fillers behind them."""
    rng = np.random.default_rng(seed)
    total = int(np.sum(lens)) + fillers
    slots = -(-total // scan.CHUNK) * scan.CHUNK
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    ends = (starts + np.asarray(lens)).astype(np.int32)

    def bf16(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.bfloat16)

    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), inner))
    return dict(
        x=bf16(slots, inner), low=bf16(slots, rank), b=bf16(slots, n),
        c=bf16(slots, n), w_dt=bf16(rank, inner, scale=rank ** -0.5),
        b_dt=jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32),
        a_log=jnp.log(jnp.broadcast_to(
            jnp.arange(1, n + 1, dtype=jnp.float32), (inner, n))
            * jnp.asarray(rng.uniform(0.5, 2.0, (inner, 1)), jnp.float32)),
        skip=jnp.asarray(rng.uniform(0.5, 1.5, inner), jnp.float32),
    ), starts, ends, slots


@pytest.mark.parametrize("lens", [
    [70, 5, 130, 1, 40],          # rows that start and end inside chunks
    [64, 128, 8, 63, 65],         # rows on and around chunk boundaries
], ids=["unaligned", "boundaries"])
def test_kernel_is_the_recurrence_across_chunks_and_row_starts(lens):
    """The Pallas kernel (interpreted) on a stream of rows of unequal
    length, non-zero fillers behind them, against the token-by-token form
    a row at a time from a zero state: outputs, every row's final state,
    zeros on the fillers."""
    ops, starts, ends, slots = _operands(0, lens)
    valid = jnp.arange(slots) < ends[-1]
    y, states = scan.selective_scan_chunked(
        ops["x"], ops["low"], ops["w_dt"], ops["b_dt"], ops["a_log"],
        ops["b"], ops["c"], ops["skip"], jnp.asarray(starts),
        jnp.asarray(ends), valid, max(lens), interpret=True)
    want, want_state = _by_rows(ops, starts, ends)
    for r, (lo, hi) in enumerate(zip(starts, ends)):
        scale = float(jnp.abs(want[r]).max())
        # the kernel's output is bfloat16: a rounding of the result
        assert float(jnp.abs(y[lo:hi].astype(jnp.float32)
                             - want[r, :hi - lo]).max()) <= 2 ** -7 * scale
    assert _state_error(states, want_state) <= STATE_TOL
    assert float(jnp.abs(y[ends[-1]:].astype(jnp.float32)).max()) == 0.0


# The kernel's states against the token-by-token form on the same operands:
# both carry float32, so only the order of float32 roundings differs.
STATE_TOL = 1e-5


def _state_error(got, want):
    """The largest difference of a row's state over its largest entry."""
    return float(jnp.max(jnp.abs(got - want).max(axis=(1, 2))
                         / jnp.abs(want).max(axis=(1, 2))))


def _by_rows(ops, starts, ends, state_dtype=None):
    """The token-by-token form a row at a time from a zero state, the rows
    padded to one length (the padding leaves a state as it is): the
    program's own form, or with ``state_dtype`` the reference's recurrence
    with the state rounded to it after every token."""
    lens = ends - starts
    width = int(lens.max())
    at = np.minimum(starts[:, None] + np.arange(width)[None, :],
                    ops["x"].shape[0] - 1)
    valid = jnp.asarray(np.arange(width)[None, :] < lens[:, None])
    x, low, b, c = (ops[k][at] for k in ("x", "low", "b", "c"))
    if state_dtype is None:
        return scan.selective_scan_recurrent(
            x, low, ops["w_dt"], ops["b_dt"], ops["a_log"], b, c,
            ops["skip"], jnp.zeros((len(lens), 256, 16), jnp.float32),
            valid)
    delta = jnp.where(valid[..., None], scan.step_sizes(
        low, ops["w_dt"], ops["b_dt"]), 0.0)
    return ref.selective_scan(
        x.astype(jnp.float32), delta, scan.decay_rates(ops["a_log"]),
        b.astype(jnp.float32), c.astype(jnp.float32),
        state_dtype=state_dtype)


def test_a_state_carried_in_bfloat16_breaks_the_kernels_tolerance():
    """The state's precision at the kernel: the reference's recurrence
    with its state rounded to bfloat16 after every token, on the kernel's
    own operands, lies outside ``STATE_TOL`` of the float32 form that the
    kernel keeps to, and the reference's float32 recurrence inside."""
    lens = [70, 5, 130, 1, 40]
    ops, starts, ends, _ = _operands(0, lens)
    _, want = _by_rows(ops, starts, ends)
    _, same = _by_rows(ops, starts, ends, jnp.float32)
    _, low = _by_rows(ops, starts, ends, jnp.bfloat16)
    assert _state_error(same, want) <= STATE_TOL
    assert _state_error(low, want) > 30 * STATE_TOL


def test_kernel_geometry_is_refused_by_name():
    assert scan.selective_scan_admits(24576, 5120)
    assert not scan.selective_scan_admits(24576 + 8, 5120)
    assert not scan.selective_scan_admits(24576, 5120 + 64)
    ops, starts, ends, _ = _operands(0, [10])
    with pytest.raises(ValueError, match="selective_scan_admits"):
        scan.selective_scan_chunked(
            ops["x"][:100], ops["low"][:100], ops["w_dt"], ops["b_dt"],
            ops["a_log"], ops["b"][:100], ops["c"][:100], ops["skip"],
            jnp.asarray(starts), jnp.asarray(ends),
            jnp.ones((100,), bool), 10, interpret=True)


# ------------------------------------------------ layers against reference

@jax.jit
def _ref_mixer(p, h, snapshot_at):
    """The reference's Mamba-1 layer, the state and tail after token
    ``snapshot_at[r]`` of row ``r``."""
    return ref.mamba_mixer(p, h, HF, snapshot_at=snapshot_at)


def test_mixer_on_the_compact_stream_matches_the_reference(clf):
    """The layer in float32 on the compact token stream (the kernel) of
    three rows of unequal length against the reference's token-by-token
    layer a row at a time on the same weights: output, state and
    convolution tail of every row, none reading its neighbour's."""
    cfg = clf.config
    p = _f32(clf.params["layer_0"]["attention"])
    lens = jnp.asarray([120, 41, 77], jnp.int32)
    rows, width = 3, 256
    packed = RealPositions.of(lens, width, 256)
    h = jax.random.normal(jax.random.key(3), (rows, width, cfg.dim))
    positions = jnp.broadcast_to(jnp.arange(width), (rows, width))
    state = SelectiveState.zeros(rows, 128, 16, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, new = jax.jit(_mixer(cfg).apply)(
            {"params": p}, packed.gather(h)[None],
            packed.gather(positions)[None], state, lens, None, packed)
        want, want_state, want_tail = _ref_mixer(p, h, lens - 1)
    got = packed.put_back(got[0])
    for r, n in enumerate(np.asarray(lens)):
        scale = float(jnp.abs(want[r, :n]).max())
        assert float(jnp.abs(got[r, :n] - want[r, :n]).max()) < (
            F32_TOL * scale)
    assert float(jnp.abs(new.state - want_state).max()) < (
        F32_TOL * float(jnp.abs(want_state).max()))
    assert float(jnp.abs(new.conv - want_tail).max()) < F32_TOL


def test_prefill_then_single_token_steps_continue_the_state(clf):
    """The layer's prefill of a prefix (kernel, padded rows) and then
    single-token calls from the state it returns equal one token-by-token
    forward over the whole sequence: output and final state."""
    cfg = clf.config
    p = _f32(clf.params["layer_1"]["attention"])
    rows, width, steps = 2, 128, 5
    lens = jnp.asarray([128, 77], jnp.int32)
    h = jax.random.normal(jax.random.key(4), (rows, width + steps, cfg.dim))
    mixer = _mixer(cfg)
    state = SelectiveState.zeros(rows, 128, 16, dtype=jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(width), (rows, width))
    apply = jax.jit(mixer.apply)
    with jax.default_matmul_precision("highest"):
        _, new = apply({"params": p}, h[:, :width], positions, state,
                       lens, lens)
        outs = []
        for t in range(steps):
            token = h[jnp.arange(rows), lens + t]
            out, new = apply({"params": p}, token[:, None], None, new)
            outs.append(out[:, 0])
        want, want_state, _ = _ref_mixer(p, h, lens - 1 + steps)
    got = jnp.stack(outs, axis=1)
    for r, n in enumerate(np.asarray(lens)):
        scale = float(jnp.abs(want[r, :n + steps]).max())
        assert float(jnp.abs(got[r] - want[r, n:n + steps]).max()) < (
            F32_TOL * scale)
    assert float(jnp.abs(new.state - want_state).max()) < (
        F32_TOL * float(jnp.abs(want_state).max()))


# ------------------------------------------- the reference against its source

def _torch_jamba(seed: int = 0):
    torch = pytest.importorskip("torch")
    modelling = pytest.importorskip(
        "transformers.models.jamba.modeling_jamba")
    from transformers.models.jamba import JambaConfig

    keys = {k: v for k, v in HF.items()
            if k not in ("_note", "runtime", "model_type")}
    config = JambaConfig(**{**keys, "use_mamba_kernels": False})
    config._attn_implementation = "eager"
    torch.manual_seed(seed)
    model = modelling.JambaForCausalLM(config).float().eval()
    with torch.no_grad():   # the assumed init's ranges, not _init_weights'
        model.model.embed_tokens.weight.normal_(0, 1.0)
        for layer in model.model.layers:
            for w in layer.parameters():
                if w.dim() == 2:
                    w.normal_(0, w.shape[1] ** -0.5)
            mamba = getattr(layer, "mamba", None)
            if mamba is not None:
                dt = torch.exp(torch.empty(128).uniform_(-6.9, -2.3))
                mamba.dt_proj.bias.copy_(dt + torch.log(-torch.expm1(-dt)))
                mamba.D.copy_(torch.empty(128).uniform_(0.5, 1.5))
                mamba.conv1d.bias.copy_(torch.empty(128).normal_() * 0.1)
                for norm in (mamba.dt_layernorm, mamba.b_layernorm,
                             mamba.c_layernorm):
                    norm.weight.copy_(torch.empty(norm.weight.shape
                                                  ).uniform_(0.5, 1.5))
    return torch, model


def _params_from_torch(model) -> dict:
    """The modelling code's weights in this repository's parameter tree:
    the mapping a checkpoint loader would make."""
    def t(w):
        return jnp.asarray(w.detach().numpy(), jnp.float32)

    params = {"tok_embeddings": {"embedding": t(model.model.embed_tokens
                                                .weight)},
              "norm": {"scale": t(model.model.final_layernorm.weight)}}
    for i, layer in enumerate(model.model.layers):
        ffn = layer.feed_forward
        entry = {
            "attention_norm": {"scale": t(layer.input_layernorm.weight)},
            "ffn_norm": {"scale": t(layer.pre_ff_layernorm.weight)},
            "feed_forward": {n: {"kernel": t(getattr(ffn, n).weight).T}
                             for n in ("gate_proj", "up_proj", "down_proj")},
        }
        mamba = getattr(layer, "mamba", None)
        if mamba is not None:
            entry["attention"] = {
                "in_proj": t(mamba.in_proj.weight).T,
                "conv": t(mamba.conv1d.weight)[:, 0].T,
                "conv_bias": t(mamba.conv1d.bias),
                "x_proj": t(mamba.x_proj.weight).T,
                "dt_proj": t(mamba.dt_proj.weight).T,
                "dt_bias": t(mamba.dt_proj.bias),
                "A_log": t(mamba.A_log), "D": t(mamba.D),
                "dt_norm": t(mamba.dt_layernorm.weight),
                "b_norm": t(mamba.b_layernorm.weight),
                "c_norm": t(mamba.c_layernorm.weight),
                "out_proj": t(mamba.out_proj.weight).T,
            }
        else:
            attn = layer.self_attn
            dim, d = 64, 16
            entry["attention"] = {
                "q_proj": {"kernel": t(attn.q_proj.weight).T.reshape(
                    dim, 4, d)},
                "k_proj": {"kernel": t(attn.k_proj.weight).T.reshape(
                    dim, 1, d)},
                "v_proj": {"kernel": t(attn.v_proj.weight).T.reshape(
                    dim, 1, d)},
                "o_proj": {"kernel": t(attn.o_proj.weight).T.reshape(
                    4, d, dim)},
            }
        params[f"layer_{i}"] = entry
    return params


def test_reference_is_transformers_jamba():
    """A tiny ``JambaForCausalLM`` (its slow path: the recurrence written
    out) with seeded weights copied into the parameter tree: the
    reference's logits within float32 rounding of the modelling code's, and
    the same weights through the PROGRAM in float32."""
    torch, model = _torch_jamba()
    params = _params_from_torch(model)
    ids = np.random.default_rng(0).integers(0, 4096, (2, 40))
    with torch.no_grad():
        want = model(torch.as_tensor(ids), use_cache=False).logits.numpy()
    read_at = np.broadcast_to(np.arange(40), (2, 40))
    got = ref.forward(params, HF, ids, read_at, rows_block=2)["logits"]
    scale = float(np.abs(want).max())
    assert scale > 0.5                     # logits that say something
    assert float(np.abs(got - want).max()) < 2e-4 * scale
    cfg = dataclasses.replace(PRESETS["jamba-tiny"](), dtype="float32",
                              param_dtype="float32", attn_impl="dense")
    with jax.default_matmul_precision("highest"):
        logits, _ = llama.LlamaModel(cfg).apply(
            {"params": params}, jnp.asarray(ids),
            jnp.broadcast_to(jnp.arange(40), (2, 40)),
            causal_mask(40, 40, 0))
    assert float(np.abs(np.asarray(logits) - want).max()) < 2e-4 * scale


# -------------------------------------------------------------- end to end

def _system(clf, lyrics, probe):
    prepared = clf.prepare(lyrics)
    _, ids, lens = prepared
    clf.probe_rows = np.asarray(probe, np.int32)
    handle = clf.launch(clf.transfer(prepared))
    scores = np.asarray(handle[1], np.float64)
    labels = clf.collect(handle)
    probe_out = {k: np.asarray(v) for k, v in handle[2]["probe"].items()}
    return np.asarray(ids), np.asarray(lens), scores, probe_out, labels


PROBE = [0, 2, 3, 5, 7, 8, 11, 12]


@pytest.fixture(scope="module")
def served(clf):
    """One compact 512-wide scoring step of 13 rows (an empty lyric last),
    as the system serves it, and the reference's judgement of its probed
    rows in float32 and in int8."""
    lyrics = _lyrics(1, 12) + [""]
    ids, lens, scores, probe, labels = _system(clf, lyrics, PROBE)
    judged = {variant: ref.label_scores(
        clf.params, HF, ids[PROBE], lens[PROBE], clf._label_ids,
        clf._label_lens, variant=variant, rows_block=8)
        for variant in ("f32", "int8")}
    return ids, lens, scores, probe, labels, judged


def test_prefill_and_label_passes_agree_with_the_full_forward(clf, served):
    """The system's prompt prefill on the compact token stream (the
    selective-scan and packed flash kernels), its states, convolution tails
    and key/value cache, and the three label continuations that fork them,
    against one plain forward a label over prompt + label tokens: every
    limit of ``TEST_TOLERANCE``."""
    ids, lens, scores, probe, labels, judged = served
    capacity = compact_capacity(int(lens.sum()), *ids.shape)
    assert llama.runs_compact(clf.config, ids.shape, capacity)
    readings = {**ref.compare(judged["f32"], scores[PROBE], probe,
                              lens[PROBE]),
                **ref.scan_readings(clf.params, HF, probe, lens[PROBE])}
    kept = ref.within(readings, ref.TEST_TOLERANCE)
    assert set(kept) == set(ref.TEST_TOLERANCE) - {"label_margin"}
    assert all(kept.values()), readings
    assert labels[-1] == "Neutral"                 # the empty lyric


def test_the_reference_in_int8_breaks_the_tolerance(served):
    """The precision below the configuration's: the reference with every
    projection fake-quantized to int8, judged against the float32 reference
    as the system is, breaks every median limit of ``TEST_TOLERANCE`` (the
    largest readings are gross-error limits)."""
    ids, lens, _, _, _, judged = served
    low = judged["int8"]
    probe = {**low["kept"], "logits": low["logits"]}
    readings = ref.compare(judged["f32"], low["scores"], probe, lens[PROBE])
    kept = ref.within(readings, ref.TEST_TOLERANCE)
    assert not any(v for name, v in kept.items() if "median" in name), (
        readings)


def _bf16_state_scan(x, low, w_dt, b_dt, a_log, b, c, skip, starts, ends,
                     valid, max_len):
    """``selective_scan_chunked`` with its state carried in bfloat16: the
    reference's recurrence a row at a time, the state rounded after every
    token, the outputs put back on the stream."""
    lens = ends - starts
    at = jnp.minimum(starts[:, None] + jnp.arange(max_len)[None, :],
                     x.shape[0] - 1)
    real = jnp.arange(max_len)[None, :] < lens[:, None]

    def rows(v):
        return v[at].astype(jnp.float32)

    delta = jnp.where(real[..., None],
                      scan.step_sizes(low[at], w_dt, b_dt), 0.0)
    y, state = ref.selective_scan(
        rows(x), delta, scan.decay_rates(a_log), rows(b), rows(c),
        snapshot_at=lens - 1, state_dtype=jnp.bfloat16)
    y = y + skip.astype(jnp.float32) * rows(x)
    out = jnp.zeros(x.shape, jnp.float32).at[
        jnp.where(real, at, x.shape[0])].set(y, mode="drop")
    return out.astype(x.dtype), state.astype(jnp.float32)


def test_a_state_carried_in_bfloat16_breaks_the_tolerance(served,
                                                          monkeypatch):
    """The negative control of the state's precision, end to end: the same
    model served with its Mamba-1 states carried in bfloat16 breaks
    ``TEST_TOLERANCE``'s ``scan_state_max`` (the first layer's state
    against the float32 recurrence on the program's own inputs to it),
    which the system keeps, as it keeps every other limit."""
    from music_analyst_tpu.engines.sentiment import get_backend
    from music_analyst_tpu.models import mamba1

    ids, lens, scores, probe, _, _ = served
    backend = get_backend("jamba-tiny")
    system = ref.scan_readings(backend.params, HF, probe, lens[PROBE])
    assert system["scan_state_max"] <= ref.TEST_TOLERANCE["scan_state_max"]
    monkeypatch.setattr(mamba1, "selective_scan_chunked", _bf16_state_scan)
    low = get_backend("jamba-tiny")
    _, low_lens, _, low_probe, _ = _system(low, _lyrics(1, 12) + [""],
                                           PROBE)
    readings = ref.scan_readings(low.params, HF, low_probe,
                                 low_lens[PROBE])
    assert not ref.within(readings, ref.TEST_TOLERANCE)["scan_state_max"], (
        readings, system)


def test_compact_stream_equals_the_padded_prefill(clf):
    """The forward that keeps its hidden state on the compact token set
    against the same call with lengths alone (the token-by-token
    recurrence on padded rows): the first layer's state and tail, the
    attention layer's keys, and the logits."""
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


def test_decode_runtimes_refuse_the_selective_state(clf):
    from music_analyst_tpu.serving.decode_runtime import (
        decode_runtime_refusal,
        slot_runtime,
    )

    refusal = decode_runtime_refusal(clf, "paged")
    assert "recurrent state" in refusal and "SelectiveState" in refusal
    with pytest.raises(NotImplementedError, match="recurrent state"):
        slot_runtime(clf)


def test_cli_writes_the_jobs_files_and_counts_what_a_step_did(tmp_path):
    from music_analyst_tpu.cli.main import main

    fixture = os.path.join(REPO, "tests", "fixtures", "mini_songs.csv")
    assert main(["sentiment", fixture, "--model", "jamba-tiny",
                 "--batch-size", "4", "--output-dir", str(tmp_path)]) == 0
    with open(tmp_path / "sentiment_totals.json", encoding="utf-8") as fh:
        assert sum(json.load(fh).values()) == 8
    with open(tmp_path / "run_manifest.json", encoding="utf-8") as fh:
        manifest = json.load(fh)
    counters, gauges = manifest["counters"], manifest["gauges"]
    # 3 Mamba-1 layers; the labels' one position a row read, 8 rows
    assert counters["mamba1.tokens"] == 3 * (
        counters["decoder.tokens_real"] - 8 * 3)
    assert counters["mamba1.state_steps"] == 8 * 3 * 1 * 3
    assert counters["traced.embeddings.tied"] > 0
    # 4 rows x 3 layers x 128 channels x (16 float32 + 3 bfloat16)
    assert gauges["recurrent_state_bytes"] == 4 * 3 * 128 * (64 + 6)
    assert gauges["kv_cache_bytes"] > 0
    # the mixer's scopes name the scoring program's device operations (the
    # gate's product is fused into the out projection, under its scope)
    from music_analyst_tpu.profiling.compile import op_scopes

    paths = " ".join(
        str(entry[0]) for program in op_scopes()
        if program["module"] == "jit__score_labels"
        for entry in program["ops"].values())
    for part in ("proj", "conv", "xproj", "scan", "out"):
        assert f"mamba1.{part}/" in paths, part
