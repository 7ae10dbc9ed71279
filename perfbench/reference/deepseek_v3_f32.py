"""Plain float32 forward of a ``model_type: deepseek_v3`` decoder: the
reference of the ``kanana-2-30b-a3b`` configuration.

Written from the published description (DeepSeek-V2 §2.1 for multi-head
latent attention, DeepSeek-V3 §2.1.2 for the sigmoid router with the
auxiliary-loss-free correction bias; the Hugging Face ``DeepseekV3ForCausalLM``
layout for the order of operations).  ``h`` is the RMS-normed input of a
sub-layer (eps from the configuration), per token:

* MLA: ``q = W_q h`` -> heads x (nope | rope); ``[c_kv | k_rope] = W_kva h``;
  ``c_kv <- RMSNorm(c_kv)``; RoPE on ``q_rope`` and the one shared ``k_rope``;
  ``[k_nope | v] = W_kvb c_kv``; ``k = [k_nope | k_rope]``;
  ``softmax(q.k / sqrt(nope + rope))`` causal, in float32; ``y = W_o (P v)``.
  Always the expanded form, full ``[rows, heads, T, T]`` scores, no cache.
* Router: ``s = sigmoid(W_r h)``; the ``top_k`` of ``s + b`` are chosen;
  weights are ``s`` (without ``b``) there, over their sum, times
  ``routed_scaling_factor``.
* Experts: every expert runs on every token and a dense ``[tokens, E]``
  weight matrix (zeros off the chosen) combines them; plus one shared SwiGLU.
  The leading ``first_k_dense_replace`` layers are a plain SwiGLU.
* Residuals as Llama, final RMSNorm, untied head.

Label scores are computed as the program defines them but with none of its
machinery: for each label one full forward over ``prompt + label`` tokens,
``log_softmax`` at the positions that predict the label's tokens, their mean
over the label's length.

No model code of the repository is imported; the weights are read from the
backend's parameter tree by name and upcast from bfloat16 inside each
layer's program, one layer at a time (and one expert at a time within it),
so the reference fits beside the resident model.  Matrix multiplications
run at ``highest`` precision.

Departures from the published code, none of which changes a value:

* RoPE follows the Hugging Face form (de-interleave the pairs, then
  ``rotate_half``), which leaves ``q_rope``/``k_rope`` in another order than
  the program's in-place pair rotation; both sides of the dot product are
  permuted alike.
* ``n_group = topk_group = 1``: the group stage selects its only group and
  is not written.

``variant="int8"`` computes the same forward with every projection and
expert matmul fake-quantized (weights per output channel, activations per
row, symmetric int8): the "nearest precision below" reading the tolerances
are set against.

Tolerances (``TOLERANCE``), with their reasons, are at the bottom.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


# ------------------------------------------------------------ primitives

def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _mm(x, w, variant: str):
    """``x [.., K] @ w [K, N]`` in float32; ``variant="int8"`` rounds the
    activations per row and the weights per output channel to int8 first."""
    x, w = x.astype(F32), w.astype(F32)
    if variant == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    return x @ w


def rms_norm(x, scale, eps: float):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope_interleaved(x, positions, theta: float):
    """``x [.., T, heads, d]`` with rotated pairs ``(x[2i], x[2i+1])``:
    de-interleave to ``[evens | odds]``, then the half-split rotation."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    angles = positions.astype(F32)[..., None] * inv_freq       # [.., T, d/2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[..., None, :]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


# ---------------------------------------------------------------- layers

def mla_attention(p, h, positions, hf: Dict, variant: str = "f32"):
    """Expanded latent attention over ``h [R, T, D]``, causal."""
    rows, n_tok, dim = h.shape
    heads, nope, rope = (hf["num_attention_heads"], hf["qk_nope_head_dim"],
                         hf["qk_rope_head_dim"])
    rank, v_dim = hf["kv_lora_rank"], hf["v_head_dim"]
    q = _mm(h, p["q_proj"]["kernel"].reshape(dim, heads * (nope + rope)),
            variant).reshape(rows, n_tok, heads, nope + rope)
    kv_a = _mm(h, p["kv_a_proj"]["kernel"], variant)
    c_kv = rms_norm(kv_a[..., :rank], p["kv_a_norm"]["scale"],
                    hf["rms_norm_eps"])
    q_rope = rope_interleaved(q[..., nope:], positions, hf["rope_theta"])
    k_rope = rope_interleaved(kv_a[..., None, rank:], positions,
                              hf["rope_theta"])                # [R,T,1,rope]
    kv = _mm(c_kv, p["kv_b_proj"]["kernel"].reshape(
        rank, heads * (nope + v_dim)), variant).reshape(
            rows, n_tok, heads, nope + v_dim)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (rows, n_tok, heads, rope))],
        -1)
    q = jnp.concatenate([q[..., :nope], q_rope], -1)
    scores = jnp.einsum("rqhd,rkhd->rhqk", q, k) / np.sqrt(nope + rope)
    causal = jnp.arange(n_tok)[None, :] <= jnp.arange(n_tok)[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    out = jnp.einsum("rhqk,rkhd->rqhd", jax.nn.softmax(scores, -1),
                     kv[..., nope:])
    return _mm(out.reshape(rows, n_tok, heads * v_dim),
               p["o_proj"]["kernel"].reshape(heads * v_dim, dim), variant)


def swiglu(p, h, variant: str = "f32"):
    gate = _mm(h, p["gate_proj"]["kernel"], variant)
    up = _mm(h, p["up_proj"]["kernel"], variant)
    return _mm(jax.nn.silu(gate) * up, p["down_proj"]["kernel"], variant)


def route(p, h, hf: Dict, prefer=None, margin: float = 0.0):
    """``(scores [.., E], chosen [.., k], combine [.., E], ties)``:
    ``combine`` is the dense weight matrix, zeros off the chosen experts.

    ``prefer [.., k]`` is another implementation's choice for the same
    tokens (``-1`` where it states none).  Where it differs from this
    router's and every expert it names scores within ``margin`` of this
    router's k-th corrected score, the two are a tie that rounding broke
    the other way: the preferred experts are taken, so that what follows
    compares arithmetic and not two sides of a coin.  A preferred expert
    further down than ``margin`` is a wrong choice: this router's own stands
    and the token is counted.  ``ties`` holds, per token, ``differs``,
    ``wrong`` and ``depth`` (how far under the k-th score the lowest
    preferred expert lies; 0 where the choices agree)."""
    scores = jax.nn.sigmoid(h.astype(F32) @ p["router"].astype(F32))
    k = hf["num_experts_per_tok"]
    corrected = scores + p["e_score_correction_bias"].astype(F32)
    top, chosen = jax.lax.top_k(corrected, k)
    ties = None
    if prefer is not None:
        stated = prefer[..., :1] >= 0
        prefer = jnp.where(stated, prefer, chosen).astype(chosen.dtype)
        differs = (jnp.sort(prefer, -1) != jnp.sort(chosen, -1)).any(-1)
        depth = top[..., -1] - jnp.take_along_axis(
            corrected, prefer, -1).min(-1)
        depth = jnp.where(differs, depth, 0.0)
        wrong = differs & (depth > margin)
        chosen = jnp.where((differs & ~wrong)[..., None], prefer, chosen)
        ties = {"differs": differs, "wrong": wrong, "depth": depth}
    weights = jnp.take_along_axis(scores, chosen, -1)
    if hf["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    weights = weights * hf["routed_scaling_factor"]
    combine = jnp.sum(
        jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32)
        * weights[..., None], axis=-2)
    return scores, chosen, combine, ties


def routed_experts(p, h, combine, variant: str = "f32"):
    """``sum_e combine[.., e] * expert_e(h)``: every expert on every token,
    one expert at a time."""

    def one(acc, expert):
        gate_w, up_w, down_w, col = expert
        hidden = jax.nn.silu(_mm(h, gate_w, variant)) * _mm(h, up_w, variant)
        return acc + _mm(hidden, down_w, variant) * col[..., None], None

    acc, _ = jax.lax.scan(
        one, jnp.zeros(h.shape, F32),
        (p["gate_experts"], p["up_experts"], p["down_experts"],
         jnp.moveaxis(combine, -1, 0)))
    return acc


def moe_ffn(p, h, hf: Dict, variant: str = "f32", prefer=None,
            margin: float = 0.0):
    _, chosen, combine, ties = route(p, h, hf, prefer, margin)
    out = routed_experts(p, h, combine, variant)
    if hf["n_shared_experts"]:
        out = out + swiglu(p["shared_experts"], h, variant)
    return out, chosen, ties


@functools.partial(jax.jit, static_argnames=(
    "hf_items", "routed", "variant", "margin"))
def _layer(p, x, positions, prefer, hf_items, routed: bool, variant: str,
           margin: float):
    hf = dict(hf_items)
    eps = hf["rms_norm_eps"]
    h = rms_norm(x, p["attention_norm"]["scale"], eps)
    x = x + mla_attention(p["attention"], h, positions, hf, variant)
    h = rms_norm(x, p["ffn_norm"]["scale"], eps)
    if routed:
        out, chosen, ties = moe_ffn(p["feed_forward_moe"], h, hf, variant,
                                    prefer, margin)
        return x + out, chosen, ties
    return x + swiglu(p["feed_forward"], h, variant), None, None


@functools.partial(jax.jit, static_argnames=("eps", "variant"))
def _head(norm, lm_head, x, read_at, eps: float, variant: str):
    """Logits ``[R, P, V]`` at the positions ``read_at [R, P]``."""
    x = jnp.take_along_axis(x, read_at[..., None], axis=1)
    return _mm(rms_norm(x, norm["scale"], eps), lm_head["kernel"], variant)


def _hashable(hf: Dict):
    return tuple(sorted((k, v) for k, v in hf.items()
                        if isinstance(v, (int, float, bool, str))))


def forward(params, hf: Dict, token_ids, read_at, variant: str = "f32",
            rows_block: int = 4, prefer=None, margin: float = 0.0):
    """Logits at ``read_at [R, P]`` of the causal forward over ``token_ids
    [R, T]``; the routed layers' choices ``[layers, R, T, k]``; and, with
    ``prefer`` (another implementation's choices in that layout, ``-1`` =
    none stated; see :func:`route`), the per-token tie record of every
    routed layer ``{differs, wrong, depth: [layers, R, T]}``.  Rows go
    through in blocks of ``rows_block``; every layer is its own program, so
    one layer's float32 temporaries are live at a time."""
    token_ids = np.asarray(token_ids, np.int32)
    read_at = np.asarray(read_at, np.int32)
    hf_items = _hashable(hf)
    first_routed = hf["first_k_dense_replace"]
    logits, choices, ties = [], [], []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, token_ids.shape[0], rows_block):
            ids = jnp.asarray(token_ids[lo:lo + rows_block])
            positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
            x = params["tok_embeddings"]["embedding"][ids].astype(F32)
            chosen_block, ties_block = [], []
            for i in range(hf["num_hidden_layers"]):
                routed = i >= first_routed
                want = None
                if routed and prefer is not None:
                    want = jnp.asarray(
                        prefer[i - first_routed, lo:lo + rows_block],
                        jnp.int32)
                x, chosen, tie = _layer(
                    params[f"layer_{i}"], x, positions, want, hf_items,
                    routed, variant, margin)
                if routed:
                    chosen_block.append(np.asarray(chosen))
                    if tie is not None:
                        ties_block.append(
                            {k: np.asarray(v) for k, v in tie.items()})
            logits.append(np.asarray(_head(
                params["norm"], params["lm_head"], x,
                jnp.asarray(read_at[lo:lo + rows_block]),
                hf["rms_norm_eps"], variant)))
            if chosen_block:
                choices.append(np.stack(chosen_block))
            if ties_block:
                ties.append({k: np.stack([t[k] for t in ties_block])
                             for k in ties_block[0]})
    out = {"logits": np.concatenate(logits), "chosen": None, "ties": None}
    if choices:
        out["chosen"] = np.concatenate(choices, axis=1)
    if ties:
        out["ties"] = {k: np.concatenate([t[k] for t in ties], axis=1)
                       for k in ties[0]}
    return out


# Depths (under the k-th corrected score) at which differing choices are
# counted, so that a probe run shows where a margin can stand.
DEPTHS = (0.002, 0.005, 0.01, 0.02, 0.05)


def _sequences(prompt_ids, prompt_lens, label_row):
    """``prompt[:len] + label`` a row, zero-padded to one width."""
    rows, width = prompt_ids.shape
    ids = np.zeros((rows, width + len(label_row)), np.int32)
    for r in range(rows):
        n = int(prompt_lens[r])
        ids[r, :n] = prompt_ids[r, :n]
        ids[r, n:n + len(label_row)] = label_row
    return ids


def prefer_from_system(chosen, chosen_labels, prompt_lens):
    """The system's choices in this reference's layout: for each label,
    ``[layers, R, W + L, k]`` with the prompt's positions from the prefill
    (``chosen [layers, R, W, k]``), the label's from its continuation
    (``chosen_labels [labels, layers, R, L, k]``) placed after the prompt,
    and ``-1`` on the padding behind."""
    chosen = np.asarray(chosen, np.int32)
    chosen_labels = np.asarray(chosen_labels, np.int32)
    layers, rows, width, k = chosen.shape
    label_width = chosen_labels.shape[3]
    out = []
    for per_label in chosen_labels:
        prefer = np.full((layers, rows, width + label_width, k), -1, np.int32)
        for r in range(rows):
            n = int(prompt_lens[r])
            prefer[:, r, :n] = chosen[:, r, :n]
            prefer[:, r, n:n + label_width] = per_label[:, r]
        out.append(prefer)
    return out


def label_scores(params, hf: Dict, prompt_ids, prompt_lens, label_ids,
                 label_lens, variant: str = "f32", rows_block: int = 4,
                 prefer=None, margin: float = 0.0) -> Dict[str, Any]:
    """The program's label scores from full forwards: ``scores [R, labels]``
    (mean log-probability of each label's tokens after the prompt),
    ``last_logits [R, V]`` (at the prompt's last token), ``chosen`` (a list,
    one ``[layers, R, W + L, k]`` a label, in :func:`prefer_from_system`'s
    layout once the padding is set to ``-1``) and, with ``prefer`` (such a
    list from another implementation), ``routing``: token-layers compared,
    how many differed, how many of those were wrong (not ties within
    ``margin``), the deepest tie seen and how many lay deeper than each of
    ``DEPTHS``."""
    prompt_ids = np.asarray(prompt_ids, np.int32)
    prompt_lens = np.asarray(prompt_lens, np.int64)
    label_ids = np.asarray(label_ids, np.int32)
    rows = prompt_ids.shape[0]
    n_labels, label_width = label_ids.shape
    scores = np.zeros((rows, n_labels), np.float64)
    last_logits, chosen = None, []
    routing = {"compared": 0, "differ": 0, "wrong": 0, "deepest_tie": 0.0,
               "deepest": 0.0, "deeper_than": {d: 0 for d in DEPTHS}}
    # position len-1+j predicts the label's token j
    read_at = (prompt_lens[:, None] - 1) + np.arange(label_width)[None, :]
    for j in range(n_labels):
        ids = _sequences(prompt_ids, prompt_lens, label_ids[j])
        out = forward(params, hf, ids, read_at, variant, rows_block,
                      None if prefer is None else prefer[j], margin)
        logp = jax.nn.log_softmax(jnp.asarray(out["logits"], F32), -1)
        picked = np.asarray(jnp.take_along_axis(
            logp, jnp.asarray(label_ids[j])[None, :, None], axis=2))[..., 0]
        n = int(label_lens[j])
        scores[:, j] = picked[:, :n].sum(axis=1) / max(n, 1)
        if j == 0:
            last_logits = out["logits"][:, 0]
        if out["chosen"] is not None:
            stated = (np.arange(ids.shape[1])[None, :]
                      < (prompt_lens[:, None] + label_width))
            chosen.append(np.where(stated[None, :, :, None],
                                   out["chosen"], -1))
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
    return {"scores": scores, "last_logits": last_logits, "chosen": chosen,
            "routing": routing if prefer is not None else None}


# ------------------------------------------------------------ tolerances
#
# The system computes the same mathematics in bfloat16 (float32 softmax,
# router and combination); the weights are the same bfloat16 values on both
# sides, so what differs is the rounding of activations.  Through the router
# that rounding also breaks ties: at 128 experts, 6 a token, the sixth and
# seventh corrected scores of a token are often closer than the rounding of
# its hidden state, the token then runs another expert, and with random
# weights another expert is another function: left alone, such flips (6% of
# the tokens of the first routed layer, half of them by the sixth: my chip
# run, PR 27) swamp every other difference.  So the comparison is made in
# two parts, neither hidden in the other:
#
# * the choices.  The system hands over the experts every compared token
#   ran (``prefer``).  Where they differ from this reference's and lie within
#   ``route_margin`` of its k-th corrected score, it is a tie and the
#   reference takes the system's experts; deeper than that is a wrong choice,
#   and ``wrong_choices`` allows none.  The margin is 2.6 times the deepest
#   tie the bfloat16 system showed; the reference computed in int8
#   (``variant="int8"``) shows over a thousand wrong choices a sample.
# * the arithmetic, given equal choices: |difference| of label scores (mean
#   log-probabilities, about -ln(vocabulary) with random weights) over the
#   sampled rows x labels.  ``label_score_median`` lies between the largest
#   median the bfloat16 system read and the smallest the int8 reference read
#   against this one; ``label_score_max`` is the gross-error limit (a
#   dropped token, a missing shared expert, a wrong mask), above the largest
#   single difference the bfloat16 system read.
#
# ``label_margin``: labels are compared only where the reference's best
# label beats its second by more than this, in score units.  The readings
# behind the numbers are in PERF.md section 4, seed by seed.
#
# Readings at the published widths on the chip (my chip runs, PR 27: 8 rows
# x 3 labels a seed, 3 seeds, 32,472-48,618 token-layers compared a seed):
# the bfloat16 system differed in 8.2-8.3% of the token-layers, deepest tie
# 0.0078 / 0.0100 / 0.0115, none wrong, median 0.0134 / 0.0049 / 0.0063,
# largest 0.0224 / 0.0172 / 0.0295 (ties left alone: 0.53-0.76); the int8
# reference 1,426 / 1,559 / 1,034 wrong choices at a margin of 0.02 (162-224
# deeper than 0.05), median 0.071 / 0.043 / 0.045, largest 0.21 / 0.12 / 0.35.
TOLERANCE = {"route_margin": 0.03, "wrong_choices": 0,
             "label_score_median": 0.027, "label_score_max": 0.07,
             "label_margin": 0.05}

# The same limits at the test size (kanana-tiny on the CPU, tests/
# test_kanana.py), from 3 x 12 rows: the bfloat16 system read deepest tie
# 0.0084-0.0129, no wrong choice, median 0.0055-0.0068, largest
# 0.024-0.049; the int8 reference 18-31 wrong choices, median 0.0185-0.031,
# largest 0.083-0.142; without the ties taken over the bfloat16 system read
# up to 0.48.  Shared experts left out read a median of 0.35-0.47.  Experts
# alone in int8 read 0.010-0.019 end to end, inside bfloat16's own rounding
# of the residual stream, so that case is held at the layer:
# ``expert_layer_median`` is the median over tokens of the largest error of
# a token's expert-layer output over the output's RMS; bfloat16 reads
# 0.0114-0.0120, int8 expert weights 0.0241-0.0251, int8 weights and
# activations 0.0337-0.0363 (2 layers x 3 seeds).
TEST_TOLERANCE = {"route_margin": 0.02, "wrong_choices": 0,
                  "label_score_median": 0.012, "label_score_max": 0.08,
                  "last_logit_median": 0.03, "expert_layer_median": 0.017}
