"""Plain float32 forward of a ``laguna`` decoder (poolside's Laguna): the
reference of the ``laguna-s-2.1`` configuration.

Written from the published ``config.json`` alone (no modelling code for
``model_type: laguna`` is on this machine: ``transformers`` 4.57.6 has none).
The configuration fixes every shape and every constant; four readings are of
function and not of shape, and each is marked **assumed** below and listed,
with its reason, under ``assumed`` in the configuration file.  Two parts can
be held to code that IS here, and tests do: YaRN's frequencies and factor to
``transformers.modeling_rope_utils._compute_yarn_parameters``, the window to
``transformers.masking_utils.sliding_window_overlay``
(``tests/test_laguna.py``).

With ``u`` the RMS-normed input of a sub-layer (eps from the configuration)::

    x_0 = E[ids]
    h = x + Attn_i(RMSNorm(x));  y = h + FFN_i(RMSNorm(h))
    logits = RMSNorm(x) W_head                          (untied)

* **Attention of kind** ``layer_types[i]`` (``H`` = the kind's entry of
  ``num_attention_heads_per_layer`` query heads over ``num_key_value_heads``
  key/value heads of ``head_dim``; RoPE = ``rope_parameters[kind]``; a window
  for ``sliding_attention``):

  1. ``q, k, v = W_q u, W_k u, W_v u``, no bias; query head ``j`` reads
     key/value head ``j // (H / H_kv)``.
  2. **assumed (c)**: ``q <- RMSNorm_d(q) * w_q``, ``k <- RMSNorm_d(k) * w_k``
     a head, one learned scale of ``head_dim`` for all heads, before RoPE.
  3. RoPE at the token's position ``p`` (absolute, from 0) on the first ``r =
     head_dim * partial_rotary_factor`` dimensions of a head, half-split over
     those ``r`` (``rotate_half``); the rest pass.  ``rope_type: default``:
     ``inv_freq_j = theta^(-2j/r)``.  ``rope_type: yarn``
     (:func:`yarn_frequencies`): the blend of ``f_j`` and ``f_j / factor``
     over the ramp between the dimensions that turn ``beta_fast`` and
     ``beta_slow`` times over ``original_max_position_embeddings``; cos and
     sin times ``attention_factor``.
  4. ``a = softmax(q k^T / sqrt(head_dim) + M)`` in float32, ``M`` causal; in
     a ``sliding_attention`` layer the query at ``p`` sees keys ``p -
     sliding_window + 1 .. p`` (``kv > q - sliding_window``).  ``o = a v``.
  5. **assumed (b)**: ``g = softplus(u W_g)`` with ``W_g [D, H]``, one scalar
     a head a token (``gating: "per-head"`` fixes the shape, not the
     nonlinearity); ``o_j <- g_j o_j``; ``Attn = W_o concat_j(o_j)``.

* **FFN**: the layers of ``mlp_only_layers`` a dense SwiGLU of
  ``intermediate_size``; the others: **assumed (a)** ``s = sigmoid(u W_r)``
  over all ``published.num_experts``; the ``num_experts_per_tok`` largest ``s
  + b`` (``b`` = ``e_score_correction_bias``); weights
  ``moe_routed_scaling_factor * s_chosen / sum(s_chosen)``; plus the shared
  expert (a SwiGLU of ``shared_expert_intermediate_size``), **assumed (d)**
  ungated.
* **The share**: ``share = (first, count)`` names the experts this chip
  holds.  Every HELD expert runs on every token and a dense ``[tokens,
  count]`` weight matrix (the router's weights at the chosen experts that are
  held, zeros elsewhere) combines them; the shared expert every share
  computes alike.  What the absent experts would add is left out and that
  partial sum goes on to the next layer, as in the program (one chip of the
  two that share a layer, without the exchange).  ``share = (0, experts)`` is
  the uncut layer.
* The vocabulary is the slice the configuration holds: the embedding's rows
  and the head's columns, and so logits, ``log_softmax`` and label scores,
  are over ``vocab_size`` ids.

Label scores as ``reference/deepseek_v3_f32.py`` computes them: for each
label one full forward over ``prompt + label`` tokens, full scores under an
explicit mask, no kernel, no cache, no batching beyond blocks of rows.  The
same forward gives what the program keeps after a prompt: every layer's keys
(normed, rotated) and values at the prompt's positions.

No model code of the repository is imported (``reference/deepseek_v3_f32``
gives the primitives the references share: the fake-int8 matmul, RMSNorm,
SwiGLU, the sigmoid router with its tie rule, the held experts one at a
time); the weights are read from the backend's parameter tree by name and
upcast inside each layer's program.  Matrix multiplications run at
``highest`` precision.

Departures from the source: none known beside the four assumed readings; an
expert's fused ``[gate | up]`` projection is two matrices here
(``gate_experts``, ``up_experts``: the parameter tree's names; a permutation
of columns under random weights).  ``variant="int8"`` computes the same
forward with every projection and expert matmul fake-quantized (weights per
output channel, activations per row, symmetric int8): the "nearest precision
below" reading.  ``omit`` leaves one part of the mathematics out, for the
CPU tests that show the comparison's limits would catch it: ``"window"``
(sliding layers run causal), ``"yarn_factor"`` (cos and sin without
``attention_factor``), ``"gate"`` (no gate on the attention output); on the
chip ``tools/window_reference_probe`` makes the same three wrong programs in
the system and judges them against this reference as published.

Tolerances (``TOLERANCE``), with their reasons, are at the bottom.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from reference.deepseek_v3_f32 import (
    DEPTHS,
    F32,
    _hashable,
    _mm,
    _sequences,
    prefer_from_system,  # noqa: F401  the system's choices in this layout
    rms_norm,
    route as route_sigmoid,
    routed_experts,
    swiglu,
)

OMISSIONS = ("window", "yarn_factor", "gate")


# ------------------------------------------------------------------- RoPE

def yarn_frequencies(group: Dict, dim: int):
    """``(inv_freq [dim / 2], attention_factor)`` of a ``rope_parameters``
    group of ``rope_type: "yarn"`` over a rotary part of ``dim`` dimensions:
    with ``f_j = theta^(-2j / dim)`` and ``c(n) = dim ln(original / (2 pi n))
    / (2 ln theta)``: ``low = max(floor(c(beta_fast)), 0)``, ``high =
    min(ceil(c(beta_slow)), dim - 1)``, ``ramp_j = clip((j - low) / (high -
    low), 0, 1)``, ``inv_freq_j = (f_j / factor) ramp_j + f_j (1 - ramp_j)``.
    numpy, float32 where the source computes in float32."""
    theta, factor = float(group["rope_theta"]), float(group["factor"])
    original = group["original_max_position_embeddings"]
    f = (1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
         ).astype(np.float32)

    def c(rotations: float) -> float:
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(c(group.get("beta_fast") or 32)), 0)
    high = min(math.ceil(c(group.get("beta_slow") or 1)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / np.float32(high - low), 0, 1).astype(np.float32)
    attention_factor = group.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return (f / np.float32(factor)) * ramp + f * (1 - ramp), float(
        attention_factor)


def rope(x, positions, group: Dict, omit=()):
    """``x [R, T, heads, d]`` rotated at ``positions [R, T]`` by the kind's
    ``rope_parameters`` group: the first ``r = d * partial_rotary_factor``
    dimensions turn, half-split over those ``r``; the rest pass."""
    d = x.shape[-1]
    r = int(d * group.get("partial_rotary_factor", 1))
    if group["rope_type"] == "yarn":
        inv_freq, factor = yarn_frequencies(group, r)
        if "yarn_factor" in omit:
            factor = 1.0
    else:
        inv_freq = 1.0 / float(group["rope_theta"]) ** (
            np.arange(0, r, 2, dtype=np.float32) / r)
        factor = 1.0
    angles = positions.astype(F32)[..., None] * jnp.asarray(inv_freq, F32)
    cos = (jnp.concatenate([jnp.cos(angles)] * 2, -1) * factor)[..., None, :]
    sin = (jnp.concatenate([jnp.sin(angles)] * 2, -1) * factor)[..., None, :]
    turned, passed = x[..., :r], x[..., r:]
    half = jnp.concatenate([-turned[..., r // 2:], turned[..., :r // 2]], -1)
    return jnp.concatenate([turned * cos + half * sin, passed], -1)


# -------------------------------------------------------------- attention

def attention_mask(n_tok: int, window: int = 0):
    """``[n_tok, n_tok]`` bool: key ``j`` seen by query ``i`` iff ``j <= i``
    and, with a window, ``j > i - window``."""
    i, j = jnp.arange(n_tok)[:, None], jnp.arange(n_tok)[None, :]
    seen = j <= i
    return seen & (j > i - window) if window else seen


def attention(p, h, positions, hf: Dict, kind: str, heads: int,
              variant: str = "f32", omit=()):
    """Grouped-query attention of ``kind`` over ``h [R, T, D]``; returns
    ``(out, k [R, T, H_kv, d], v [R, T, H_kv, d])``, ``k`` as the cache
    holds it (normed and rotated)."""
    rows, n_tok, dim = h.shape
    kv_heads, d = hf["num_key_value_heads"], hf["head_dim"]
    eps = hf["rms_norm_eps"]

    def project(name, n):
        return _mm(h, p[name]["kernel"].reshape(dim, n * d), variant
                   ).reshape(rows, n_tok, n, d)

    q, k, v = (project("q_proj", heads), project("k_proj", kv_heads),
               project("v_proj", kv_heads))
    q = rms_norm(q, p["q_norm"]["scale"].astype(F32), eps)   # assumed (c)
    k = rms_norm(k, p["k_norm"]["scale"].astype(F32), eps)
    group = hf["rope_parameters"][kind]
    q, k = rope(q, positions, group, omit), rope(k, positions, group, omit)
    repeat = heads // kv_heads
    scores = jnp.einsum("rqhd,rkhd->rhqk", q, jnp.repeat(k, repeat, 2)
                        ) / np.sqrt(d)
    window = (hf["sliding_window"]
              if kind == "sliding_attention" and "window" not in omit else 0)
    scores = jnp.where(attention_mask(n_tok, window)[None, None], scores,
                       -jnp.inf)
    out = jnp.einsum("rhqk,rkhd->rqhd", jax.nn.softmax(scores, -1),
                     jnp.repeat(v, repeat, 2))
    if "gate" not in omit:                                    # assumed (b)
        gate = jax.nn.softplus(h.astype(F32) @ p["g_proj"].astype(F32))
        out = out * gate[..., None]
    return _mm(out.reshape(rows, n_tok, heads * d),
               p["o_proj"]["kernel"].reshape(heads * d, dim), variant), k, v


# ---------------------------------------------------------------- experts

def share_of(hf: Dict):
    """``(first, count)``: the experts this configuration's chip holds."""
    held = (hf.get("model") or {}).get("experts_held")
    return tuple(held) if held else (0, hf["num_experts"])


def moe_ffn(p, h, hf: Dict, share, variant: str = "f32", prefer=None,
            margin: float = 0.0, shared: bool = True):
    """This share's part of the layer's feed-forward half: the router over
    ALL experts (assumed (a): sigmoid scores, the selection bias, the chosen
    renormalised times ``moe_routed_scaling_factor``), the held experts'
    weighted sum, and (``shared``) the shared expert, which every share
    computes alike and the layer counts once (assumed (d): ungated)."""
    keys = {"num_experts_per_tok": hf["num_experts_per_tok"],
            "norm_topk_prob": hf["norm_topk_prob"],
            "routed_scaling_factor": hf["moe_routed_scaling_factor"]}
    _, chosen, combine, ties = route_sigmoid(p, h, keys, prefer, margin)
    first, count = share
    out = routed_experts(p, h, combine[..., first:first + count], variant)
    if shared:
        out = out + swiglu(p["shared_experts"], h, variant)
    return out, chosen, ties


# ------------------------------------------------------------------ model

@functools.partial(jax.jit, static_argnames=(
    "hf_items", "rope_items", "kind", "heads", "routed", "share", "variant",
    "margin", "omit"))
def _layer(p, x, positions, prefer, hf_items, rope_items, kind: str,
           heads: int, routed: bool, share, variant: str, margin: float,
           omit):
    hf = dict(hf_items)
    hf["rope_parameters"] = {kind: dict(rope_items)}
    eps = hf["rms_norm_eps"]
    h = rms_norm(x, p["attention_norm"]["scale"], eps)
    mixed, keys, values = attention(p["attention"], h, positions, hf, kind,
                                    heads, variant, omit)
    x = x + mixed
    h = rms_norm(x, p["ffn_norm"]["scale"], eps)
    kept = {"keys": keys, "values": values}
    if routed:
        out, chosen, ties = moe_ffn(p["feed_forward_moe"], h, hf, share,
                                    variant, prefer, margin)
        return x + out, kept, chosen, ties
    return x + swiglu(p["feed_forward"], h, variant), kept, None, None


@functools.partial(jax.jit, static_argnames=("eps", "variant"))
def _head(norm, lm_head, x, read_at, eps: float, variant: str):
    """Logits ``[R, P, V]`` at the positions ``read_at [R, P]``."""
    x = jnp.take_along_axis(x, read_at[..., None], axis=1)
    return _mm(rms_norm(x, norm["scale"], eps), lm_head["kernel"], variant)


def forward(params, hf: Dict, token_ids, read_at, variant: str = "f32",
            rows_block: int = 4, prefer=None, margin: float = 0.0,
            omit=()):
    """Logits at ``read_at [R, P]`` of the forward over ``token_ids [R,
    T]``; the routed layers' choices ``[routed layers, R, T, k]``; with
    ``prefer`` the per-token tie record of every routed layer; and ``kept``:
    every layer's ``keys`` / ``values [layers, R, T, H_kv, d]``.  Rows go
    through in blocks of ``rows_block``; every layer is its own program."""
    token_ids = np.asarray(token_ids, np.int32)
    read_at = np.asarray(read_at, np.int32)
    hf_items, share = _hashable(hf), share_of(hf)
    kinds, heads = hf["layer_types"], hf["num_attention_heads_per_layer"]
    dense = set(hf.get("mlp_only_layers") or ())
    omit = tuple(sorted(omit))
    if set(omit) - set(OMISSIONS):
        raise ValueError(f"omit names {omit}: known are {OMISSIONS}")
    logits, choices, ties = [], [], []
    kept = {"keys": [], "values": []}
    with jax.default_matmul_precision("highest"):
        for lo in range(0, token_ids.shape[0], rows_block):
            ids = jnp.asarray(token_ids[lo:lo + rows_block])
            positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
            x = params["tok_embeddings"]["embedding"][ids].astype(F32)
            chosen_block, ties_block = [], []
            kept_block = {name: [] for name in kept}
            routed_seen = 0
            for i, kind in enumerate(kinds):
                routed = i not in dense
                want = None
                if routed and prefer is not None:
                    want = jnp.asarray(
                        prefer[routed_seen, lo:lo + rows_block], jnp.int32)
                x, held, chosen, tie = _layer(
                    params[f"layer_{i}"], x, positions, want, hf_items,
                    _hashable(hf["rope_parameters"][kind]), kind, heads[i],
                    routed, share, variant, margin, omit)
                for name, value in held.items():
                    kept_block[name].append(np.asarray(value))
                if routed:
                    routed_seen += 1
                    chosen_block.append(np.asarray(chosen))
                    if tie is not None:
                        ties_block.append(
                            {k: np.asarray(v) for k, v in tie.items()})
            logits.append(np.asarray(_head(
                params["norm"], params["lm_head"], x,
                jnp.asarray(read_at[lo:lo + rows_block]),
                hf["rms_norm_eps"], variant)))
            for name, values in kept_block.items():
                kept[name].append(np.stack(values))
            if chosen_block:
                choices.append(np.stack(chosen_block))
            if ties_block:
                ties.append({k: np.stack([t[k] for t in ties_block])
                             for k in ties_block[0]})
    out = {"logits": np.concatenate(logits), "chosen": None, "ties": None,
           "kept": {name: np.concatenate(blocks, axis=1)
                    for name, blocks in kept.items()}}
    if choices:
        out["chosen"] = np.concatenate(choices, axis=1)
    if ties:
        out["ties"] = {k: np.concatenate([t[k] for t in ties], axis=1)
                       for k in ties[0]}
    return out


def label_scores(params, hf: Dict, prompt_ids, prompt_lens, label_ids,
                 label_lens, variant: str = "f32", rows_block: int = 4,
                 prefer=None, margin: float = 0.0,
                 omit=()) -> Dict[str, Any]:
    """The program's label scores from full forwards: ``scores [R,
    labels]`` (mean log-probability of each label's tokens after the
    prompt), ``kept`` (from the first label's forward: every layer's keys
    and values, whose prompt positions do not depend on the label),
    ``chosen`` (a list, one ``[routed layers, R, W + L, k]`` a label, ``-1``
    on the padding) and, with ``prefer``, ``routing``: token-layers
    compared, how many differed, how many of those were wrong (not ties
    within ``margin``), the deepest tie seen and how many lay deeper than
    each of ``DEPTHS``."""
    prompt_ids = np.asarray(prompt_ids, np.int32)
    prompt_lens = np.asarray(prompt_lens, np.int64)
    label_ids = np.asarray(label_ids, np.int32)
    rows = prompt_ids.shape[0]
    n_labels, label_width = label_ids.shape
    scores = np.zeros((rows, n_labels), np.float64)
    kept, chosen = None, []
    routing = {"compared": 0, "differ": 0, "wrong": 0, "deepest_tie": 0.0,
               "deepest": 0.0, "deeper_than": {d: 0 for d in DEPTHS}}
    # position len-1+j predicts the label's token j
    read_at = (prompt_lens[:, None] - 1) + np.arange(label_width)[None, :]
    for j in range(n_labels):
        ids = _sequences(prompt_ids, prompt_lens, label_ids[j])
        out = forward(params, hf, ids, read_at, variant, rows_block,
                      None if prefer is None else prefer[j], margin, omit)
        logp = jax.nn.log_softmax(jnp.asarray(out["logits"], F32), -1)
        picked = np.asarray(jnp.take_along_axis(
            logp, jnp.asarray(label_ids[j])[None, :, None], axis=2))[..., 0]
        n = int(label_lens[j])
        scores[:, j] = picked[:, :n].sum(axis=1) / max(n, 1)
        if j == 0:
            kept = out["kept"]
        stated = (np.arange(ids.shape[1])[None, :]
                  < (prompt_lens[:, None] + label_width))
        chosen.append(np.where(stated[None, :, :, None], out["chosen"], -1))
        if out["ties"] is not None:
            ties = out["ties"]
            routing["compared"] += int(stated.sum()) * len(out["chosen"])
            routing["differ"] += int(ties["differs"].sum())
            routing["wrong"] += int(ties["wrong"].sum())
            routing["deepest"] = max(routing["deepest"],
                                     float(ties["depth"].max()))
            for d in DEPTHS:
                routing["deeper_than"][d] += int((ties["depth"] > d).sum())
            routing["deepest_tie"] = max(routing["deepest_tie"], float(
                np.where(ties["wrong"], 0.0, ties["depth"]).max()))
    return {"scores": scores, "kept": kept, "chosen": chosen,
            "routing": routing if prefer is not None else None}


def _relative(got, want):
    """Largest absolute difference over the largest absolute entry."""
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-12)


def compare_kept(kept, probe, prompt_lens) -> Dict[str, float]:
    """What the program kept after the prompts (``probe``: ``keys`` /
    ``values [layers, R, S, H_kv, d]``) against this reference's, a number
    a kind: the median and the largest, over (layer, row), of a row's
    largest absolute difference on the prompt's positions over its largest
    absolute entry there."""
    out = {}
    lens = np.asarray(prompt_lens)
    for name in ("keys", "values"):
        got = np.asarray(probe[name], np.float64)
        errs = [_relative(got[layer, r, :n], kept[name][layer, r, :n])
                for layer in range(got.shape[0]) for r, n in enumerate(lens)]
        out[f"{name}_median"] = float(np.median(errs))
        out[f"{name}_max"] = float(np.max(errs))
    return out


# The names ``compare_kept`` returns that a limit of ``TOLERANCE`` bounds.
KEPT_LIMITS = ("keys_median", "keys_max", "values_median", "values_max")


# ------------------------------------------------------------ tolerances
#
# The system computes the same mathematics in bfloat16 (float32 softmax,
# gate, router and combination; the flash kernel's products in float32 from
# bfloat16 operands); the weights are the same bfloat16 values on both sides,
# so what differs is the rounding of activations.  Through the router that
# rounding also breaks ties (which 10 of 256), and with random weights another
# expert is another function.  So, as in ``deepseek_v3_f32.py``, the
# comparison is made in parts, none hidden in another:
#
# * the choices.  The system hands over the experts every compared token ran
#   (``prefer``); where they differ from this reference's and lie within
#   ``route_margin`` of its k-th corrected SCORE it is a tie and the reference
#   takes the system's experts; deeper is a wrong choice and ``wrong_choices``
#   allows none.
# * what the prefill leaves behind, which is where the precision and every
#   part of the attention's mathematics show: every layer's keys and values on
#   the prompt's positions (a row's largest error over its largest entry).
#   ``keys_median`` / ``values_median`` and ``keys_max`` / ``values_max`` lie
#   between the bfloat16 system's reading and the int8 reference's; a row
#   that read a neighbour, a mask off by one, a window not applied, a gate
#   or a factor left out read far above all four.
# * the arithmetic, given equal choices: |difference| of the three label
#   scores (mean log-probabilities over the vocabulary's slice).
#
# ``label_margin``: labels are compared only where the reference's best label
# beats its second by more than this, twice ``label_score_max`` (two scores
# may each be off).
#
# Readings at the published widths on the chip (my chip runs, PR 39: 8 rows x
# 3 labels of a 1,024-wide step, seven of the eight rows longer than 640
# tokens; two corpora of ``tools/window_reference_probe`` and the set-up of
# two runs of the cell; 69,876-75,432 token-layers compared).  The bfloat16
# system: 15% of the token-layers differ, none deeper than 0.01, the deepest
# 0.0088 / 0.0085 / 0.0096 / 0.0085; scores median 0.0063 / 0.0041 / 0.0080 /
# 0.0073, largest 0.0247 / 0.0250 / 0.0282 / 0.0181; keys median 0.0159 /
# 0.0153 / 0.0162 / 0.0157 and largest 0.0196 / 0.0234 / 0.0210 / 0.0201; values
# median 0.0149 / 0.0144 / 0.0148 / 0.0147 and largest 0.0185 / 0.0186 / 0.0180 /
# 0.0195.  The int8 reference against the same steps: 54-55% differ, 1,338 /
# 971 deeper than 0.02 (the deepest 0.045 / 0.042); scores median 0.0454 /
# 0.0365, largest 0.097 / 0.075; keys median 0.0714 / 0.0699 and largest 0.089 /
# 0.095; values median 0.0682 / 0.0652 and largest 0.100 / 0.084: int8 fails
# every limit but ``label_margin``.  The reference with a part of the
# mathematics left out (``omit``), against the same steps: the window layers
# run causal: 3,532 / 2,572 choices deeper than 0.02, scores median 0.109 /
# 0.071 and largest 0.35 / 0.25, keys median 0.115 / 0.106 and largest 0.18 /
# 0.21, values 0.108 / 0.093 and 0.19 / 0.19 (the mildest of the three: a row of
# 780 tokens loses a tenth of its pairs); without YaRN's factor: every choice
# wrong but 0.3%, scores median 0.25 / 0.40, keys median 0.94 / 0.98, values
# 0.88 / 0.87; without the gate: scores median 0.37 / 0.45, keys 0.91 / 0.90,
# values 0.87 / 0.86.  Each of the four fails every limit.  ``route_margin``
# is twice the deepest tie the bfloat16 system showed (below); every ``_median`` limit
# is near the geometric mean of its two readings (bfloat16's largest, int8's
# smallest); ``label_score_max``, ``keys_max`` and ``values_max`` lie between
# the largest of each side, about twice the bfloat16 system's largest.
# Twenty-two more set-ups of the cell (my chip runs, PR 39: twenty-one at 16 rows
# a step, one at 32) read, at the worst: deepest tie 0.0120, scores median
# 0.0128 and largest 0.0372, keys median 0.0168 and largest 0.0230, values
# median 0.0153 and largest 0.0219.
#
# The same controls THROUGH THE CELL'S OWN COMPARISON (``drivers/
# batch_job_window.against_reference`` / ``judge``: these limits, ties handed
# over within ``route_margin`` and no deeper; ``tools/window_reference_probe``
# on two corpora, my chip run, PR 39 after review), the three wrong programs
# made IN THE SYSTEM this time (the same parameters under a configuration
# with the part left out, compiled and run as the timed step) against this
# reference as published.  The system: ``correct`` on both (scores median
# 0.0079 / 0.0033, keys median 0.0163 / 0.0151, no wrong choice).  The int8
# reference: ``correct: false`` on both, 397 / 406 wrong choices, keys median
# 0.075 / 0.077 and largest 0.16 / 0.17, values 0.067 / 0.066 and 0.14 / 0.18,
# scores largest 0.112 / 0.094, median 0.039 / 0.020: it failed every limit
# on one corpus and every one but ``label_score_median`` on the other.  The
# window layers run causal: ``correct: false``, 2,306 / 1,690 wrong choices,
# keys median 0.169 / 0.116 and largest 0.31, values 0.161 / 0.106, scores
# median 0.082 / 0.046 and largest 0.33 / 0.15: every limit but ``labels``.
# Without YaRN's factor: 73,329 / 68,703 wrong choices, keys median 0.87 /
# 0.90, scores median 0.41 / 0.33; without the gate: 73,491 / 68,856, keys
# 0.90 / 0.88, scores 0.41 / 0.26: every limit, wrong labels among them.
# (The system's own ``quant="int8"`` is not the precision control: it
# quantizes the attention projections and the dense SwiGLU and leaves
# ``RoutedMoE``'s grouped matmuls, 87% of this chip's weights, in bfloat16.)
TOLERANCE = {"route_margin": 0.025, "wrong_choices": 0,
             "label_score_median": 0.022, "label_score_max": 0.06,
             "label_margin": 0.12,
             "keys_median": 0.034, "keys_max": 0.05,
             "values_median": 0.031, "values_max": 0.045}

# The same limits at the test size (laguna-tiny on the CPU, the kernel under
# the interpreter: tests/test_laguna.py), from 3 seeds x 13 rows at a 512-wide
# step (compact stream) and 3 at a narrow one (padded rows), the ties within
# 0.12 handed over so that every depth shows.  The bfloat16 system read
# deepest ties 0.017-0.030 (corrected scores); scores median 0.006-0.0135 and
# largest 0.030-0.060; keys median 0.0176-0.022 and largest 0.042-0.073;
# values median 0.0147-0.0183 and largest 0.030-0.047 (a rehearsal of the cell:
# keys 0.0238, values 0.0183).  The int8 reference: deepest 0.068-0.110;
# scores median 0.024-0.039 and largest 0.095-0.161; keys median 0.059-0.076
# and largest 0.11-0.20; values median 0.055-0.060 and largest 0.076-0.143: it
# fails ``route_margin`` (wrong choices), ``label_score_median``,
# ``keys_median`` and ``values_median``.  The reference with a part left out
# (``omit``), against the same steps: window layers run causal read keys
# median 0.92-1.09 and largest 1.39-1.68, values 0.72-0.88; without YaRN's
# factor keys 0.51-0.63 / 0.97-1.26, values 0.52-0.53; without the gate keys
# 1.22-1.24 / 1.53-1.58, values 0.92-0.95; scores median 0.15-0.55: each fails
# every limit, the gross ones (``keys_max``, ``values_max``) among them.
TEST_TOLERANCE = {"route_margin": 0.05, "wrong_choices": 0,
                  "label_score_median": 0.02, "label_score_max": 0.12,
                  "label_margin": 0.24,
                  "keys_median": 0.037, "keys_max": 0.3,
                  "values_median": 0.032, "values_max": 0.3}
