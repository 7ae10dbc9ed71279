"""Plain float32 forward of a ``jamba`` decoder (AI21's Jamba): the reference
of the ``ai21-jamba2-3b`` configuration.

Written from the published descriptions: Mamba (arXiv:2312.00752) for the
selective scan, Jamba (arXiv:2403.19887) for its norms and the layer order,
and for the order of operations the modelling code
``transformers.models.jamba`` (``JambaMambaMixer.slow_forward``,
``JambaAttention``, ``JambaMLP``, ``JambaMambaDecoderLayer``,
``JambaAttentionDecoderLayer``, ``JambaForCausalLM``), against which a test
holds this file at a tiny size.  With ``h`` the RMS-normed input of a
sub-layer (eps from the configuration)::

    x_0 = E[ids]
    x <- x + Mixer_i(RMSNorm(x));  x <- x + SwiGLU(RMSNorm(x))
    logits = RMSNorm(x) E^T                             (tied embeddings)

``Mixer_i`` is attention where ``i % attn_layer_period == attn_layer_offset``
(``JambaConfig.layers_block_type``), else Mamba-1; every feed-forward half is
the dense SwiGLU of ``intermediate_size`` (``num_experts`` 1).

* **Mamba-1** (inner width ``I = mamba_expand * hidden_size``, state ``N =
  mamba_d_state``, rank ``R = mamba_dt_rank``): ``[x | z] = h W_in``; ``x <-
  SiLU(conv(x) + b)``, a depthwise causal convolution of ``mamba_d_conv``
  taps over time (inputs before a row's first token are zero); ``[r | B |
  C] = x W_x``, each RMS-normed with its own weight; ``delta = softplus(r
  W_dt + b_dt) [I]``, ``A = -exp(A_log) [I, N]``.  Then, with ``S [I, N]``
  zero before the first token, **token by token**: ``S <- exp(delta_t (x) A)
  o S + (delta_t o x_t) (x) B_t``; ``y_t = S C_t + D o x_t``.  ``out = (y *
  SiLU(z)) W_out``.
* **Attention**: ``q, k, v = h W_q, h W_k, h W_v`` (``H_q | H_kv | H_kv``
  heads of ``d = hidden_size / H_q``, no bias, **no rotary**: Jamba's
  attention has no position signal), query head ``i`` reads key head ``i //
  (H_q / H_kv)``, causal softmax of ``q k^T / sqrt(d)`` in float32, ``W_o``.

Label scores as ``reference/granite_hybrid_f32.py`` computes them: for each
label one full forward over ``prompt + label`` tokens.  The same forward
gives the logits at the prompt's last position and what the program keeps
after a prompt: every Mamba-1 layer's state after the prompt's last token
(a snapshot taken inside the token loop) and its convolution's last
``mamba_d_conv - 1`` inputs there, and the attention layers' keys and values
at the prompt's positions.

No model code of the repository is imported; the weights are read from the
backend's parameter tree by name and upcast inside each layer's program,
one layer at a time.  Matrix multiplications run at ``highest`` precision.

Departures from the source, none of which changes a value:

* the source's ``dt_proj`` is a ``Linear`` with a bias; here its matrix and
  its bias are two parameters (``dt_proj``, ``dt_bias``), as in the
  parameter tree;
* the source's modules are run with ``use_mamba_kernels`` off (its slow
  path, the recurrence written out), which is what this file writes;
  ``num_logits_to_keep`` is unread: the logits are read where asked.

Two variants compute something else on purpose, for the readings a
tolerance stands between: ``variant="int8"`` fake-quantizes every
projection's operands (weights per output channel, activations per row,
symmetric int8: the precision below the configuration's bfloat16), and
``variant="bf16_state"`` carries every Mamba-1 state in bfloat16 from token
to token (what the configuration keeps in float32).

Tolerances (``TOLERANCE``), with their reasons, are at the bottom.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from reference.deepseek_v3_f32 import (
    F32,
    _hashable,
    _mm,
    _sequences,
    rms_norm,
    swiglu,
)


def layer_types(hf: Dict):
    """``"attention"`` or ``"mamba"`` a layer, by ``JambaConfig``'s rule."""
    period, offset = hf["attn_layer_period"], hf["attn_layer_offset"]
    return tuple("attention" if i % period == offset else "mamba"
                 for i in range(hf["num_hidden_layers"]))


# --------------------------------------------------------------- Mamba-1

def selective_scan(x, delta, a, b, c, snapshot_at=None,
                   state_dtype=F32):
    """The recurrence, a token a step.  ``x, delta [R, T, I]``, ``a [I,
    N]``, ``b, c [R, T, N]``; returns ``(y [R, T, I], S [R, I, N])``: ``S``
    after the last token, or after token ``snapshot_at[r]`` of row ``r``.
    ``state_dtype`` is what ``S`` is rounded to after every token."""
    rows, _, inner = x.shape

    def step(carry, token):
        s, kept = carry
        t, x_t, d_t, b_t, c_t = token
        s = (jnp.exp(d_t[..., None] * a) * s.astype(F32)
             + (d_t * x_t)[..., None] * b_t[:, None, :]).astype(state_dtype)
        if snapshot_at is not None:
            kept = jnp.where((snapshot_at == t)[:, None, None], s, kept)
        return (s, kept), jnp.einsum("rin,rn->ri", s.astype(F32), c_t)

    zero = jnp.zeros((rows, inner, b.shape[-1]), state_dtype)
    (s, kept), y = jax.lax.scan(
        step, (zero, zero),
        (jnp.arange(x.shape[1]),) + tuple(
            jnp.moveaxis(v, 1, 0) for v in (x, delta, b, c)))
    return jnp.moveaxis(y, 0, 1), (s if snapshot_at is None else kept)


def short_conv(u, weight, bias):
    """Depthwise causal convolution over time with bias, zero before the
    first token: ``y_t = b + sum_i weight[i] * u_{t - (K-1) + i}``.  ``u [R,
    T, W]``, ``weight [K, W]``."""
    taps = weight.shape[0]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return bias.astype(F32) + sum(
        padded[:, i:i + u.shape[1]] * weight[i].astype(F32)
        for i in range(taps))


def mamba_mixer(p, h, hf: Dict, variant: str = "f32", snapshot_at=None):
    """Mamba-1 over ``h [R, T, D]``; returns ``(out [R, T, D], state [R, I,
    N], tail [R, K-1, I])``: the state and the convolution's last inputs
    after token ``snapshot_at[r]`` (the last without)."""
    rows, n_tok, dim = h.shape
    inner, n, rank = (hf["mamba_expand"] * dim, hf["mamba_d_state"],
                      hf["mamba_dt_rank"])
    eps, keep = hf["rms_norm_eps"], hf["mamba_d_conv"] - 1
    matmul_variant = "int8" if variant == "int8" else "f32"
    both = _mm(h, p["in_proj"], matmul_variant)
    before, gate = both[..., :inner], both[..., inner:]
    x = jax.nn.silu(short_conv(before, p["conv"], p["conv_bias"]))
    chosen = _mm(x, p["x_proj"], matmul_variant)
    low = rms_norm(chosen[..., :rank], p["dt_norm"].astype(F32), eps)
    b = rms_norm(chosen[..., rank:rank + n], p["b_norm"].astype(F32), eps)
    c = rms_norm(chosen[..., rank + n:], p["c_norm"].astype(F32), eps)
    delta = jax.nn.softplus(_mm(low, p["dt_proj"], matmul_variant)
                            + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["A_log"].astype(F32))
    y, state = selective_scan(
        x, delta, a, b, c, snapshot_at,
        jnp.bfloat16 if variant == "bf16_state" else F32)
    y = (y + p["D"].astype(F32) * x) * jax.nn.silu(gate)
    last = (jnp.full((rows,), n_tok - 1) if snapshot_at is None
            else snapshot_at)
    at = last[:, None] - keep + 1 + jnp.arange(keep)[None, :]       # [R, K-1]
    tail = jnp.where(
        (at >= 0)[..., None],
        jnp.take_along_axis(before, jnp.maximum(at, 0)[..., None], axis=1),
        0.0)
    return (_mm(y, p["out_proj"], matmul_variant), state.astype(F32),
            tail)


# -------------------------------------------------------------- attention

def attention(p, h, hf: Dict, variant: str = "f32"):
    """Causal grouped-query attention without positions over ``h [R, T,
    D]``; returns ``(out, k [R, T, H_kv, d], v [R, T, H_kv, d])``."""
    rows, n_tok, dim = h.shape
    heads, kv_heads = hf["num_attention_heads"], hf["num_key_value_heads"]
    d = dim // heads

    def project(name, n):
        return _mm(h, p[name]["kernel"].reshape(dim, n * d), variant
                   ).reshape(rows, n_tok, n, d)

    q, k, v = (project("q_proj", heads), project("k_proj", kv_heads),
               project("v_proj", kv_heads))
    group = heads // kv_heads
    scores = jnp.einsum("rqhd,rkhd->rhqk", q, jnp.repeat(k, group, 2)
                        ) / np.sqrt(d)
    causal = jnp.arange(n_tok)[None, :] <= jnp.arange(n_tok)[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    out = jnp.einsum("rhqk,rkhd->rqhd", jax.nn.softmax(scores, -1),
                     jnp.repeat(v, group, 2))
    return _mm(out.reshape(rows, n_tok, heads * d),
               p["o_proj"]["kernel"].reshape(heads * d, dim), variant), k, v


# ------------------------------------------------------------------ model

@functools.partial(jax.jit, static_argnames=("hf_items", "mixer", "variant"))
def _layer(p, x, snapshot_at, hf_items, mixer: str, variant: str):
    hf = dict(hf_items)
    eps = hf["rms_norm_eps"]
    matmul_variant = "int8" if variant == "int8" else "f32"
    h = rms_norm(x, p["attention_norm"]["scale"], eps)
    if mixer == "mamba":
        mixed, state, tail = mamba_mixer(p["attention"], h, hf, variant,
                                         snapshot_at)
        kept = {"state": state, "conv": tail}
    else:
        mixed, keys, values = attention(p["attention"], h, hf,
                                        matmul_variant)
        kept = {"keys": keys, "values": values}
    x = x + mixed
    h = rms_norm(x, p["ffn_norm"]["scale"], eps)
    return x + swiglu(p["feed_forward"], h, matmul_variant), kept


@functools.partial(jax.jit, static_argnames=("eps", "variant"))
def _head(norm, embedding, x, read_at, eps: float, variant: str):
    """Logits ``[R, P, V]`` at the positions ``read_at [R, P]``: the tied
    head."""
    x = jnp.take_along_axis(x, read_at[..., None], axis=1)
    return _mm(rms_norm(x, norm["scale"], eps), embedding.T,
               "int8" if variant == "int8" else "f32")


def forward(params, hf: Dict, token_ids, read_at, snapshot_at=None,
            variant: str = "f32", rows_block: int = 1):
    """Logits at ``read_at [R, P]`` of the causal forward over ``token_ids
    [R, T]``, and ``kept``: every Mamba-1 layer's ``state [mamba layers, R,
    I, N]`` and ``conv [mamba layers, R, K-1, I]`` after token
    ``snapshot_at[r]`` (the last without) and every attention layer's
    ``keys`` / ``values [attention layers, R, T, H_kv, d]``.  Rows go
    through in blocks of ``rows_block``; every layer is its own program."""
    token_ids = np.asarray(token_ids, np.int32)
    read_at = np.asarray(read_at, np.int32)
    hf_items = _hashable(hf)
    embedding = params["tok_embeddings"]["embedding"]
    logits = []
    kept = {"state": [], "conv": [], "keys": [], "values": []}
    with jax.default_matmul_precision("highest"):
        for lo in range(0, token_ids.shape[0], rows_block):
            ids = jnp.asarray(token_ids[lo:lo + rows_block])
            snap = (None if snapshot_at is None else jnp.asarray(
                np.asarray(snapshot_at)[lo:lo + rows_block], jnp.int32))
            x = embedding[ids].astype(F32)
            kept_block = {name: [] for name in kept}
            for i, mixer in enumerate(layer_types(hf)):
                x, held = _layer(params[f"layer_{i}"], x, snap, hf_items,
                                 mixer, variant)
                for name, value in held.items():
                    kept_block[name].append(np.asarray(value))
            logits.append(np.asarray(_head(
                params["norm"], embedding, x,
                jnp.asarray(read_at[lo:lo + rows_block]),
                hf["rms_norm_eps"], variant)))
            for name, values in kept_block.items():
                if values:
                    kept[name].append(np.stack(values))
    return {"logits": np.concatenate(logits),
            "kept": {name: np.concatenate(blocks, axis=1)
                     for name, blocks in kept.items() if blocks}}


def label_scores(params, hf: Dict, prompt_ids, prompt_lens, label_ids,
                 label_lens, variant: str = "f32",
                 rows_block: int = 1) -> Dict[str, Any]:
    """The program's label scores from full forwards: ``scores [R,
    labels]`` (mean log-probability of each label's tokens after the
    prompt), ``logits [R, V]`` at the prompt's last position and ``kept``
    (what the Mamba-1 layers hold after the prompt's last token and the
    attention layers' keys and values), both from the first label's
    forward: neither depends on the label."""
    prompt_ids = np.asarray(prompt_ids, np.int32)
    prompt_lens = np.asarray(prompt_lens, np.int64)
    label_ids = np.asarray(label_ids, np.int32)
    rows = prompt_ids.shape[0]
    n_labels, label_width = label_ids.shape
    scores = np.zeros((rows, n_labels), np.float64)
    first = None
    # position len-1+j predicts the label's token j
    read_at = (prompt_lens[:, None] - 1) + np.arange(label_width)[None, :]
    for j in range(n_labels):
        ids = _sequences(prompt_ids, prompt_lens, label_ids[j])
        out = forward(params, hf, ids, read_at, prompt_lens - 1, variant,
                      rows_block)
        logp = jax.nn.log_softmax(jnp.asarray(out["logits"], F32), -1)
        picked = np.asarray(jnp.take_along_axis(
            logp, jnp.asarray(label_ids[j])[None, :, None], axis=2))[..., 0]
        n = int(label_lens[j])
        scores[:, j] = picked[:, :n].sum(axis=1) / max(n, 1)
        if first is None:
            first = out
    return {"scores": scores, "logits": first["logits"][:, 0],
            "kept": first["kept"]}


def _relative(got, want, axes):
    """Root-mean-square difference over the root-mean-square entry, over
    ``axes``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(np.mean((got - want) ** 2, axis=axes)) / np.maximum(
        np.sqrt(np.mean(want ** 2, axis=axes)), 1e-30)


def compare(judged, scores, probe, prompt_lens) -> Dict[str, float]:
    """What the program computed for the compared rows against this
    reference's ``judged`` (:func:`label_scores`), a number a kind:
    ``score_median`` / ``score_max`` the absolute difference of the label
    scores; ``logits_*`` a row's relative difference of the last prompt
    position's logits (``probe["logits"] [R, V]``); ``state_*`` a Mamba-1
    layer's state of a row (``probe["state"] [layers, R, I, N]``),
    ``conv_*`` its convolution's tail, ``keys_*`` / ``values_*`` an attention
    layer's keys and values of a row on the prompt's positions: median and
    largest over (layer, row) of the relative difference (root-mean-square
    difference over root-mean-square entry)."""
    out = {}
    diff = np.abs(np.asarray(scores, np.float64) - judged["scores"])
    out["score_median"], out["score_max"] = (
        float(np.median(diff)), float(diff.max()))
    kept = judged["kept"]
    parts = {
        "logits": [_relative(probe["logits"], judged["logits"], -1)],
        "state": [_relative(probe["state"], kept["state"], (-1, -2))],
        "conv": [_relative(probe["conv"], kept["conv"], (-1, -2))],
    }
    lens = np.asarray(prompt_lens)
    for name in ("keys", "values"):
        got = np.asarray(probe[name], np.float64)
        parts[name] = [np.array(
            [_relative(got[layer, r, :n], kept[name][layer, r, :n], None)
             for layer in range(got.shape[0]) for r, n in enumerate(lens)])]
    for name, (err,) in parts.items():
        out[f"{name}_median"] = float(np.median(err))
        out[f"{name}_max"] = float(np.max(err))
    return out


@functools.partial(jax.jit, static_argnames=("state_dtype",))
def _scan_state(p, x, low, b, c, last, state_dtype):
    with jax.default_matmul_precision("highest"):
        delta = jax.nn.softplus(low @ p["dt_proj"].astype(F32)
                                + p["dt_bias"].astype(F32))
        return selective_scan(x, delta, -jnp.exp(p["A_log"].astype(F32)),
                              b, c, last, state_dtype)[1]


def scan_state(params, hf: Dict, probe, prompt_lens, state_dtype=F32):
    """The first Mamba-1 layer's state ``[R, I, N]`` after each probed row's
    prompt, from this file's recurrence run on what that layer's recurrence
    read in the program (``probe["scan_x"]``, ``["scan_r"]`` (the normed
    ``r`` before ``dt_proj``), ``["scan_b"]``, ``["scan_c"] [R, T, .]``),
    its state rounded to ``state_dtype`` after every token."""
    first = layer_types(hf).index("mamba")
    inputs = [jnp.asarray(probe[f"scan_{name}"], F32)
              for name in ("x", "r", "b", "c")]
    return np.asarray(_scan_state(
        params[f"layer_{first}"]["attention"], *inputs,
        jnp.asarray(np.asarray(prompt_lens) - 1, jnp.int32),
        jnp.dtype(state_dtype)), np.float64)


def scan_readings(params, hf: Dict, probe, prompt_lens) -> Dict[str, float]:
    """``scan_state_max``: the first Mamba-1 layer's state of each probed
    row (``probe["state"][0]``) against :func:`scan_state` in float32 on
    the program's own inputs to it, largest over rows of the relative
    difference.  The inputs are the program's, so the reading is the
    recurrence's own arithmetic: the state's precision, which the rounding
    of the activations upstream hides from every reading of
    :func:`compare`."""
    want = scan_state(params, hf, probe, prompt_lens)
    err = _relative(probe["state"][0], want, (-1, -2))
    return {"scan_state_max": float(np.max(err))}


def within(readings: Dict[str, float], tolerance: Dict[str, float]) -> Dict:
    """Each limit of ``tolerance`` that :func:`compare`'s ``readings`` keep
    (``True``) or break."""
    return {name: readings[name] <= limit
            for name, limit in tolerance.items() if name in readings}


# ------------------------------------------------------------ tolerances
#
# The system computes the same mathematics in bfloat16 (float32 states,
# steps, decays and softmax; bfloat16 projections, convolution output,
# ``r``, ``B`` and ``C``); the weights are the same bfloat16 values on both
# sides, so what differs is the rounding of activations.  There is no router:
# every difference is arithmetic.  The comparison is made in parts, none
# hidden in another (``compare``): the three label scores, the logits at the
# prompt's last position, and what the prefill leaves behind (every Mamba-1
# layer's float32 state and its convolution's tail, the attention layers'
# keys and values), each a median and a largest over (layer, row).
#
# ``label_margin``: labels are compared only where the reference's best
# label beats its second by more than this, twice ``score_max`` (two scores
# may each be off).
#
# ``scan_state_max`` (``scan_readings``) reruns the first Mamba-1 layer's
# recurrence in float32 on the program's own inputs to it, so that only the
# recurrence's arithmetic differs: it is the one number that sees the
# state's precision.  Carried in bfloat16, a state reads under the system
# on every number of ``compare`` (the activations' rounding is larger).
#
# The limits stand between two readings at the published widths on a TPU
# v5e.  The bfloat16 system over 8 seeds (``tools/mamba1_reference_probe.py``
# on 2, the cell's own comparison in 6 runs; 8 probed rows of a 64-row step,
# 1,024 wide, each): scores median 0.0130-0.0227, largest 0.047-0.083;
# logits median 0.038-0.049, largest 0.050-0.064; states median
# 0.0538-0.0557, largest 0.082-0.105; tails median 0.031-0.034, largest
# 0.049-0.053; keys 0.0306-0.0311; values 0.0307-0.0314; ``scan_state_max``
# 3.3e-7-6.6e-7.  The int8 reference (the probe's 2 seeds): scores median
# 0.127-0.154, largest 0.59-0.60; logits median 0.295-0.329, largest 0.436;
# states median 0.391-0.395, largest 0.60-0.69; tails median 0.238-0.240,
# largest 0.40-0.42; keys 0.236; values 0.236-0.238: every reading at least
# 5.6 times the system's largest, and int8 breaks every limit but
# ``scan_state_max`` (its recurrence is float32).  The recurrence with its
# state carried in bfloat16 on the system's own inputs (the probe's 2
# seeds): ``scan_state_max`` 0.0056-0.0067.  Each limit lies between the
# system's largest reading and the control's least, at least 2 times from
# each.
TOLERANCE = {"score_median": 0.06, "score_max": 0.25,
             "logits_median": 0.11, "logits_max": 0.2,
             "state_median": 0.125, "state_max": 0.3,
             "conv_median": 0.075, "conv_max": 0.15,
             "keys_median": 0.07, "values_median": 0.07,
             "scan_state_max": 1e-4, "label_margin": 0.5}

# The same limits at the test size (jamba-tiny on the CPU, the kernels
# under the interpreter: tests/test_jamba.py), from 3 seeds x 8 probed rows
# of a 13-row step on the compact stream at 512 wide.  The bfloat16 system
# read: scores median 0.0049-0.0070, largest 0.021-0.060; logits median
# 0.0122-0.0135, largest 0.016-0.029; states median 0.0122-0.0125, largest
# 0.024-0.036; convolution tails median 0.0077-0.0082, largest 0.017-0.025;
# keys 0.0112-0.0113; values 0.0110-0.0112.  The int8 reference: scores
# median 0.025-0.029, largest 0.097-0.156; logits median 0.047-0.066, largest
# 0.081-0.119; states median 0.0529-0.0536, largest 0.10-0.18; tails median
# 0.0323-0.0327, largest 0.064-0.076; keys 0.043-0.044; values 0.042-0.043.
# Every ``_median`` limit lies near the geometric mean of its two readings,
# every ``_max`` limit between the largest of one side and the least of the
# other.  The reference with its state carried in bfloat16 read states median
# 0.0056-0.0058 and every other number of ``compare`` under the system's.
# ``scan_state_max``: the system 2.3e-8-5.2e-8, the system with its state
# carried in bfloat16 0.0055-0.0065 (3 seeds of the lyrics).
TEST_TOLERANCE = {"score_median": 0.012, "score_max": 0.08,
                  "logits_median": 0.025, "logits_max": 0.05,
                  "state_median": 0.025, "state_max": 0.065,
                  "conv_median": 0.016, "conv_max": 0.045,
                  "keys_median": 0.022, "values_median": 0.022,
                  "scan_state_max": 1e-5, "label_margin": 0.16}
