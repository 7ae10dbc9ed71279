"""Plain float32 forward of the ``ling_hybrid`` decoder (Ling-3.0-flash-VL's
language model): the reference of the ``ling-3.0-flash-vl`` configuration.

Written from the published descriptions: Kimi Linear (arXiv:2510.26692) for
Kimi Delta Attention, DeepSeek-V2 section 2.1 for multi-head latent attention,
DeepSeek-V3 section 2.1.2 and ``transformers``' ``DeepseekV3TopkRouter`` for the
sigmoid router with the correction bias and the group-limited choice.  Layer
``i`` (counted on the published indices the configuration keeps, ``model.
layer_ids``) with ``h`` the RMS-normed input of a sub-layer, eps from the
configuration::

    x <- x + Mixer_i(RMSNorm(x));  x <- x + FFN_i(RMSNorm(x))

``Mixer_i`` is MLA where ``(i + 1) % layer_group_size == 0``, else KDA;
``FFN_i`` a dense SwiGLU in the leading ``first_k_dense_replace`` layers, the
routed layer in the rest.

* **KDA** (``H`` heads of ``d`` key and value channels, a token ``t``):
  ``q~, k~, v~ = W_q h, W_k h, W_v h``; ``q, k, v = SiLU(conv(.))``, a
  depthwise causal convolution of ``short_conv_kernel_size`` taps over time
  (inputs before a row's first token are zero); ``q, k`` L2-normalised a
  head (``x * rsqrt(sum x^2 + 1e-6)``), ``q`` times ``d^-0.5``; ``beta =
  sigmoid(W_b h) [H]``; ``g = kda_lower_bound * sigmoid(exp(A_log_h) * (W_f
  h + dt_bias)) [H, d]``.  Then, a head, with ``S [d, d]`` zero before the
  first token, **token by token**: ``S <- Diag(exp(g_t)) S``; ``S <- S +
  beta_t k_t (v_t - k_t^T S)^T``; ``o_t = S^T q_t``.  ``o_t <-
  RMSNorm_d(o_t) * w_norm * sigmoid(W_g h)``; ``y = W_o o``.  No RoPE.
* **MLA**: as ``reference/deepseek_v3_f32.py`` (expanded form, full scores,
  no cache) plus one gate a head before ``o_proj``: ``o_h <- o_h *
  sigmoid(W_gate h)_h``.
* **Router**: ``s = sigmoid(W_r h)`` over all ``num_experts`` (the
  published count: ``published.num_experts``); on ``s + b`` the experts in
  ``n_group`` runs of neighbours, a group's score the sum of its two largest,
  the best ``topk_group`` groups kept, the others' scores counted as 0, the
  ``num_experts_per_tok`` largest chosen; weights ``s`` (without ``b``)
  there, over their sum, times ``routed_scaling_factor``.
* **Experts, the share**: ``share = (first, count)`` names the experts this
  chip holds.  Every HELD expert runs on every token and a dense ``[tokens,
  count]`` weight matrix (the router's weights at the chosen experts that are
  held, zeros elsewhere) combines them; plus the shared SwiGLU.  What the
  absent experts would add is left out and that partial sum goes on to the
  next layer, as in the program (one chip of the four that share a layer,
  without the exchange).  ``share = (0, num_experts)`` is the uncut layer.
* The vocabulary is the slice the configuration holds: logits, ``log_softmax``
  and label scores are over ``vocab_size`` ids.
* Residuals as Llama, final RMSNorm, untied head.

Label scores as ``reference/deepseek_v3_f32.py`` computes them: for each
label one full forward over ``prompt + label`` tokens.  The same forward
also gives what the program keeps after a prompt: every KDA layer's state
after the prompt's last token (a snapshot taken inside the token loop) and
the MLA layers' latents ``c_kv`` (normed) and ``k_rope`` (rotated) at the
prompt's positions.

No model code of the repository is imported (``reference/deepseek_v3_f32``
gives the primitives both references share: the fake-int8 matmul, RMSNorm,
RoPE in the Hugging Face form, SwiGLU); the weights are read from the
backend's parameter tree by name and upcast inside each layer's program,
one layer at a time and one expert at a time within it.  Matrix
multiplications run at ``highest`` precision.

Departures from the source, none of which changes a value unless listed
under ``assumed`` in the configuration file:

* RoPE in the Hugging Face form (de-interleave, ``rotate_half``): the rope
  columns come out in another order than the program's, alike on both sides
  of every dot product; ``k_rope`` is therefore compared after the same
  de-interleaving (:func:`deinterleave`).
* ``variant="int8"`` computes the same forward with every projection and
  expert matmul fake-quantized (weights per output channel, activations per
  row, symmetric int8): the "nearest precision below" reading.

Tolerances (``TOLERANCE``), with their reasons, are at the bottom.
"""

from __future__ import annotations

import functools
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
    rope_interleaved,
    swiglu,
)


def deinterleave(x):
    """The program's rope columns ``(x0, x1, x2, ..)`` in this reference's
    order ``[evens | odds]``."""
    return np.concatenate([x[..., 0::2], x[..., 1::2]], -1)


# ------------------------------------------------------------------- KDA

def delta_rule(q, k, v, g, beta, snapshot_at=None):
    """The gated delta rule, a token a step.  ``q, k, g [R, T, H, d]``, ``v
    [R, T, H, d]``, ``beta [R, T, H]``; returns ``(o [R, T, H, d], S [R, H,
    d, d])``: ``S`` after the last token, or after token ``snapshot_at[r]``
    of row ``r``."""
    rows, n_tok, heads, d = q.shape

    def step(carry, token):
        s, kept = carry
        t, q_t, k_t, v_t, g_t, b_t = token
        s = s * jnp.exp(g_t)[..., None]                 # decay, a channel
        read = jnp.einsum("rhk,rhkv->rhv", k_t, s)
        s = s + k_t[..., None] * (b_t[..., None] * (v_t - read))[..., None, :]
        if snapshot_at is not None:
            kept = jnp.where((snapshot_at == t)[:, None, None, None], s, kept)
        return (s, kept), jnp.einsum("rhk,rhkv->rhv", q_t, s)

    zero = jnp.zeros((rows, heads, d, d), F32)
    (s, kept), o = jax.lax.scan(
        step, (zero, zero),
        (jnp.arange(n_tok),) + tuple(
            jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), (s if snapshot_at is None else kept)


def short_conv(u, weight):
    """Depthwise causal convolution over time, zero before the first
    token: ``y_t = sum_i weight[i] * u_{t - (K-1) + i}``.  ``u [R, T, W]``,
    ``weight [K, W]``."""
    taps = weight.shape[0]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, i:i + u.shape[1]] * weight[i].astype(F32)
               for i in range(taps))


def kda_attention(p, h, hf: Dict, variant: str = "f32", snapshot_at=None):
    """KDA over ``h [R, T, D]``; returns ``(y [R, T, D], state)``."""
    rows, n_tok, _ = h.shape
    heads, d = hf["num_attention_heads"], hf["head_dim"]

    def heads_of(x):
        return x.reshape(rows, n_tok, heads, d)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q, k, v = (heads_of(jax.nn.silu(short_conv(
        _mm(h, p[f"{n}_proj"], variant), p[f"{n}_conv"]))) for n in "qkv")
    q, k = unit(q) * d ** -0.5, unit(k)
    beta = jax.nn.sigmoid(_mm(h, p["b_proj"], variant))
    g = hf["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["A_log"].astype(F32))[:, None]
        * heads_of(_mm(h, p["f_proj"], variant) + p["dt_bias"].astype(F32)))
    o, state = delta_rule(q, k, v, g, beta, snapshot_at)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + hf["rms_norm_eps"]) * p["o_norm"].astype(F32)
    gate = jax.nn.sigmoid(_mm(h, p["g_proj"], variant))
    return _mm(o.reshape(rows, n_tok, heads * d) * gate, p["o_proj"],
               variant), state


# ------------------------------------------------------------------- MLA

def mla_attention(p, h, positions, hf: Dict, variant: str = "f32"):
    """Expanded latent attention over ``h [R, T, D]``, causal, with the
    head-wise output gate; returns ``(y, c_kv [R, T, rank], k_rope [R, T,
    rope])``."""
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
    gate = jax.nn.sigmoid(_mm(h, p["gate_proj"]["kernel"], variant))
    out = out * gate[..., None]
    return (_mm(out.reshape(rows, n_tok, heads * v_dim),
                p["o_proj"]["kernel"].reshape(heads * v_dim, dim), variant),
            c_kv, k_rope[:, :, 0])


# ---------------------------------------------------------------- experts

def choose(scores, bias, hf: Dict):
    """The group-limited choice: ``(chosen [.., k], group_scores [.., G],
    corrected [.., E])``."""
    corrected = scores + bias.astype(F32)
    groups = hf["n_group"]
    grouped = corrected.reshape(corrected.shape[:-1] + (groups, -1))
    group_scores = jax.lax.top_k(grouped, 2)[0].sum(-1)
    kept = _kept_groups(group_scores, hf["topk_group"])
    masked = jnp.where(kept[..., None], grouped, 0.0).reshape(corrected.shape)
    _, chosen = jax.lax.top_k(masked, hf["num_experts_per_tok"])
    return chosen, group_scores, corrected


def _kept_groups(group_scores, topk_group: int):
    _, best = jax.lax.top_k(group_scores, topk_group)
    return jax.nn.one_hot(best, group_scores.shape[-1], dtype=bool).any(-2)


def route(p, h, hf: Dict, share, prefer=None, margin: float = 0.0):
    """``(chosen [.., k], combine [.., count], ties)``: ``combine`` is the
    dense weight matrix over the HELD experts, zeros off the chosen.

    ``prefer [.., k]`` is another implementation's choice for the same
    tokens (``-1`` where it states none).  Where it differs from this
    router's, it is a tie that rounding broke the other way if there is a
    set of ``topk_group`` groups, holding every group it draws from, such
    that (a) each of them scores within ``2 * margin`` (a group's score is a
    sum of two) of this router's ``topk_group``-th group and (b) with those
    groups kept, every expert it names scores within ``margin`` of the k-th
    corrected score: the preferred experts are then taken, so that what
    follows compares arithmetic and not two sides of a coin.  Anything
    deeper is a wrong choice: this router's own stands and the token is
    counted.  ``ties`` holds, per token, ``differs``, ``wrong`` and
    ``depth`` (the larger of the expert's depth and half the group's; 0
    where the choices agree)."""
    scores = jax.nn.sigmoid(h.astype(F32) @ p["router"].astype(F32))
    k, n_experts = hf["num_experts_per_tok"], scores.shape[-1]
    chosen, group_scores, corrected = choose(
        scores, p["e_score_correction_bias"], hf)
    ties = None
    if prefer is not None:
        stated = prefer[..., :1] >= 0
        prefer = jnp.where(stated, prefer, chosen).astype(chosen.dtype)
        differs = (jnp.sort(prefer, -1) != jnp.sort(chosen, -1)).any(-1)
        groups, top_g = hf["n_group"], hf["topk_group"]
        grouped = corrected.reshape(corrected.shape[:-1] + (groups, -1))
        drawn = jax.nn.one_hot(prefer // (n_experts // groups), groups,
                               dtype=bool).any(-2)
        threshold = jax.lax.top_k(group_scores, top_g)[0][..., -1]
        # The groups the other side kept are known only as far as it drew
        # experts from them: every way of filling them up to ``topk_group``
        # is tried (the groups drawn from first, then one candidate, then by
        # score), and the shallowest reading counts.
        depth = None
        for candidate in range(groups):
            kept = _kept_groups(
                group_scores + 1e3 * drawn
                + 1e2 * (jnp.arange(groups) == candidate), top_g)
            masked = jnp.where(kept[..., None], grouped, 0.0).reshape(
                corrected.shape)
            experts = jax.lax.top_k(masked, k)[0][..., -1] - (
                jnp.take_along_axis(masked, prefer, -1).min(-1))
            group_depth = jnp.maximum(threshold - jnp.where(
                kept, group_scores, jnp.inf).min(-1), 0.0)
            this = jnp.maximum(experts, group_depth / 2)
            depth = this if depth is None else jnp.minimum(depth, this)
        depth = jnp.where(differs, depth, 0.0)
        wrong = differs & (depth > margin)
        chosen = jnp.where((differs & ~wrong)[..., None], prefer, chosen)
        ties = {"differs": differs, "wrong": wrong, "depth": depth}
    weights = jnp.take_along_axis(scores, chosen, -1)
    if hf["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    weights = weights * hf["routed_scaling_factor"]
    first, count = share
    combine = jnp.sum(
        jax.nn.one_hot(chosen - first, count, dtype=F32)  # absent: no column
        * weights[..., None], axis=-2)
    return chosen, combine, ties


def routed_experts(p, h, combine, variant: str = "f32"):
    """``sum_e combine[.., e] * expert_e(h)`` over the held experts: every
    one on every token, one expert at a time."""

    def one(acc, expert):
        gate_w, up_w, down_w, col = expert
        hidden = jax.nn.silu(_mm(h, gate_w, variant)) * _mm(h, up_w, variant)
        return acc + _mm(hidden, down_w, variant) * col[..., None], None

    acc, _ = jax.lax.scan(
        one, jnp.zeros(h.shape, F32),
        (p["gate_experts"], p["up_experts"], p["down_experts"],
         jnp.moveaxis(combine, -1, 0)))
    return acc


def moe_ffn(p, h, hf: Dict, share, variant: str = "f32", prefer=None,
            margin: float = 0.0, shared: bool = True):
    """This share's part of the routed layer: the held experts' weighted
    sum and (``shared``) the shared SwiGLU, which every share computes
    alike."""
    chosen, combine, ties = route(p, h, hf, share, prefer, margin)
    out = routed_experts(p, h, combine, variant)
    if shared:
        out = out + swiglu(p["shared_experts"], h, variant)
    return out, chosen, ties


# ------------------------------------------------------------------ model

def layer_kinds(hf: Dict):
    """``[(mixer, routed)]`` of the layers the configuration keeps."""
    layers = hf["num_hidden_layers"]
    ids = (hf.get("model") or {}).get("layer_ids") or list(range(layers))
    return [("mla" if (ids[i] + 1) % hf["layer_group_size"] == 0 else "kda",
             i >= hf["first_k_dense_replace"]) for i in range(layers)]


def share_of(hf: Dict):
    """``(first, count)``: the experts this configuration's chip holds."""
    held = (hf.get("model") or {}).get("experts_held")
    return tuple(held) if held else (0, hf["num_experts"])


@functools.partial(jax.jit, static_argnames=(
    "hf_items", "mixer", "routed", "share", "variant", "margin"))
def _layer(p, x, positions, snapshot_at, prefer, hf_items, mixer: str,
           routed: bool, share, variant: str, margin: float):
    hf = dict(hf_items)
    eps = hf["rms_norm_eps"]
    h = rms_norm(x, p["attention_norm"]["scale"], eps)
    if mixer == "kda":
        mixed, state = kda_attention(p["attention"], h, hf, variant,
                                     snapshot_at)
        kept = {"state": state}
    else:
        mixed, c_kv, k_rope = mla_attention(p["attention"], h, positions, hf,
                                            variant)
        kept = {"latents": c_kv, "rope_keys": k_rope}
    x = x + mixed
    h = rms_norm(x, p["ffn_norm"]["scale"], eps)
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


def forward(params, hf: Dict, token_ids, read_at, snapshot_at=None,
            variant: str = "f32", rows_block: int = 4, prefer=None,
            margin: float = 0.0):
    """Logits at ``read_at [R, P]`` of the causal forward over ``token_ids
    [R, T]``; the routed layers' choices ``[layers, R, T, k]``; with
    ``prefer`` the per-token tie record of every routed layer; and
    ``kept``: every KDA layer's state after token ``snapshot_at[r]`` (the
    last without) ``[kda layers, R, H, d, d]`` and every MLA layer's
    ``latents`` / ``rope_keys`` ``[mla layers, R, T, .]``.  Rows go through
    in blocks of ``rows_block``; every layer is its own program."""
    token_ids = np.asarray(token_ids, np.int32)
    read_at = np.asarray(read_at, np.int32)
    hf_items = _hashable(hf)
    kinds, share = layer_kinds(hf), share_of(hf)
    logits, choices, ties = [], [], []
    kept = {"state": [], "latents": [], "rope_keys": []}
    with jax.default_matmul_precision("highest"):
        for lo in range(0, token_ids.shape[0], rows_block):
            ids = jnp.asarray(token_ids[lo:lo + rows_block])
            positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
            snap = (None if snapshot_at is None else jnp.asarray(
                np.asarray(snapshot_at)[lo:lo + rows_block], jnp.int32))
            x = params["tok_embeddings"]["embedding"][ids].astype(F32)
            chosen_block, ties_block = [], []
            kept_block = {name: [] for name in kept}
            n_routed = 0
            for i, (mixer, routed) in enumerate(kinds):
                want = None
                if routed and prefer is not None:
                    want = jnp.asarray(
                        prefer[n_routed, lo:lo + rows_block], jnp.int32)
                x, held, chosen, tie = _layer(
                    params[f"layer_{i}"], x, positions, snap, want, hf_items,
                    mixer, routed, share, variant, margin)
                for name, value in held.items():
                    kept_block[name].append(np.asarray(value))
                if routed:
                    n_routed += 1
                    chosen_block.append(np.asarray(chosen))
                    if tie is not None:
                        ties_block.append(
                            {k: np.asarray(v) for k, v in tie.items()})
            logits.append(np.asarray(_head(
                params["norm"], params["lm_head"], x,
                jnp.asarray(read_at[lo:lo + rows_block]),
                hf["rms_norm_eps"], variant)))
            for name, values in kept_block.items():
                if values:
                    kept[name].append(np.stack(values))
            if chosen_block:
                choices.append(np.stack(chosen_block))
            if ties_block:
                ties.append({k: np.stack([t[k] for t in ties_block])
                             for k in ties_block[0]})
    out = {"logits": np.concatenate(logits), "chosen": None, "ties": None,
           "kept": {name: np.concatenate(blocks, axis=1)
                    for name, blocks in kept.items() if blocks}}
    if choices:
        out["chosen"] = np.concatenate(choices, axis=1)
    if ties:
        out["ties"] = {k: np.concatenate([t[k] for t in ties], axis=1)
                       for k in ties[0]}
    return out


def label_scores(params, hf: Dict, prompt_ids, prompt_lens, label_ids,
                 label_lens, variant: str = "f32", rows_block: int = 4,
                 prefer=None, margin: float = 0.0) -> Dict[str, Any]:
    """The program's label scores from full forwards: ``scores [R,
    labels]`` (mean log-probability of each label's tokens after the
    prompt), ``kept`` (from the first label's forward: the KDA states after
    the prompt's last token, the MLA latents and rope keys, whose prompt
    positions do not depend on the label), ``chosen`` (a list, one
    ``[layers, R, W + L, k]`` a label, ``-1`` on the padding) and, with
    ``prefer``, ``routing``: token-layers compared, how many differed, how
    many of those were wrong (not ties within ``margin``), the deepest tie
    seen and how many lay deeper than each of ``DEPTHS``."""
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
        out = forward(params, hf, ids, read_at, prompt_lens - 1, variant,
                      rows_block, None if prefer is None else prefer[j],
                      margin)
        logp = jax.nn.log_softmax(jnp.asarray(out["logits"], F32), -1)
        picked = np.asarray(jnp.take_along_axis(
            logp, jnp.asarray(label_ids[j])[None, :, None], axis=2))[..., 0]
        n = int(label_lens[j])
        scores[:, j] = picked[:, :n].sum(axis=1) / max(n, 1)
        if j == 0:
            kept = out["kept"]
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
    return {"scores": scores, "kept": kept, "chosen": chosen,
            "routing": routing if prefer is not None else None}


def compare_kept(kept, probe, prompt_lens) -> Dict[str, float]:
    """What the program kept after the prompts (``probe``: ``state [kda
    layers, R, H, d, d]``, ``latents`` / ``rope_keys [mla layers, R, S,
    .]``) against this reference's, a number a kind: for the states the
    median and the largest, over (layer, row, head), of a head's largest
    absolute difference over the head's largest absolute entry; for the
    latents and rope keys the same over (layer, row) on the prompt's
    positions."""
    out = {}
    want = kept["state"]
    got = np.asarray(probe["state"], np.float64)
    err = np.abs(got - want).max(axis=(-1, -2)) / np.maximum(
        np.abs(want).max(axis=(-1, -2)), 1e-12)
    out["state_median"], out["state_max"] = (
        float(np.median(err)), float(err.max()))
    lens = np.asarray(prompt_lens)
    for name in ("latents", "rope_keys"):
        got = np.asarray(probe[name], np.float64)
        if name == "rope_keys":
            got = deinterleave(got)
        errs = []
        for layer in range(got.shape[0]):
            for r, n in enumerate(lens):
                a, b = got[layer, r, :n], kept[name][layer, r, :n]
                errs.append(np.abs(a - b).max() / max(np.abs(b).max(), 1e-12))
        out[f"{name}_median"] = float(np.median(errs))
        out[f"{name}_max"] = float(np.max(errs))
    return out


# ------------------------------------------------------------ tolerances
#
# The system computes the same mathematics in bfloat16 (float32 states,
# decays, softmax, router and combination; bfloat16 MXU operands in the KDA
# kernel); the weights are the same bfloat16 values on both sides, so what
# differs is the rounding of activations.  Through the router that rounding
# also breaks ties, here at two levels (which 4 of 8 groups, then which 8 of
# their 256 experts: a quarter of the compared token-layers differ), and
# with random weights another expert is another function.  So, as in
# ``deepseek_v3_f32.py``, the comparison is made in parts, none hidden in
# another:
#
# * the choices.  The system hands over the experts every compared token
#   ran (``prefer``); where they differ from this reference's and lie within
#   ``route_margin`` of its k-th corrected score (a group within twice that
#   of its 4th group's score) it is a tie and the reference takes the
#   system's experts; deeper is a wrong choice and ``wrong_choices`` allows
#   none.  The margin is twice the deepest tie the bfloat16 system showed.
# * the arithmetic, given equal choices: |difference| of the three label
#   scores (mean log-probabilities over the 39,296-id slice) over the
#   sampled rows: ``label_score_median`` between the two readings,
#   ``label_score_max`` a gross-error limit above the largest single one.
# * what the prefill leaves behind: every KDA layer's float32 state after
#   the prompt's last token and the MLA layer's latents and rope keys, each
#   as a head's (a row's) largest error over its largest entry;
#   ``state_median`` / ``latents_median`` / ``rope_keys_median`` lie between
#   the two readings, ``state_max`` is a gross-error limit (a row that read
#   a neighbour's state, a chunk taken twice: the fault this comparison
#   found on the chip read 0.88-0.99) above the largest the bfloat16 system
#   read: a single head of a single row whose state is small.
#
# ``label_margin``: labels are compared only where the reference's best
# label beats its second by more than this, twice ``label_score_max``'s
# reading (two scores may each be off).
#
# Readings at the published widths on the chip (my chip runs, PR 33: 8 rows
# x 3 labels of a 64 x 1,024 step, four corpora, 32,724-48,996 token-layers
# compared each).  The bfloat16 system: 24-26% of the token-layers differ,
# deepest tie 0.0148 / 0.0157 / 0.0167 / 0.0200, none wrong; scores median
# 0.0162 / 0.0188 / 0.0229 / 0.0272, largest 0.0516-0.0619; states median
# 0.0289-0.0313, largest 0.169-0.268; latents median 0.0351-0.0394; rope
# keys 0.0351-0.0370.  The int8 reference against the same steps: 1,216 /
# 1,760 / 1,793 wrong choices at 0.03 (126-177 deeper than 0.05); scores
# median 0.050 / 0.064 / 0.102, largest 0.21-0.25; states median
# 0.128-0.134, largest 0.58-0.74; latents median 0.185-0.189; rope keys
# 0.178-0.181.  int8 fails every limit but ``label_margin``.
TOLERANCE = {"route_margin": 0.04, "wrong_choices": 0,
             "label_score_median": 0.038, "label_score_max": 0.12,
             "label_margin": 0.15,
             "state_median": 0.06, "state_max": 0.42,
             "latents_median": 0.08, "rope_keys_median": 0.08}

# The same limits at the test size (ling-tiny on the CPU, the kernels under
# the interpreter: tests/test_ling_hybrid.py), from 3 seeds x 13 rows at a
# 512-wide step (compact stream) and at a narrow one (padded rows).  At a
# hidden size of 64 with 16 experts in 4 groups near-ties are common and the
# bfloat16 stream's rounding is a larger share of everything.  The bfloat16
# system read deepest ties 0.020-0.031, 0-3 choices deeper than 0.05 (three
# at 0.057 in one of the six), scores median 0.0096-0.0167 and largest
# 0.042-0.064, states median 0.0158-0.0181 and largest 0.066-0.085,
# latents median 0.044-0.054, rope keys 0.037-0.040; the int8 reference
# 7-106 choices deeper than 0.05 (23-106 at the wide step), scores median
# 0.036-0.054 and largest 0.10-0.21, states 0.041-0.052 and 0.15-0.23,
# latents 0.123-0.140, rope keys 0.109-0.128.  ``state_max`` is a
# gross-error limit (a row that read a neighbour's state, a missing decay),
# not a precision one.
TEST_TOLERANCE = {"route_margin": 0.05, "wrong_choices": 5,
                  "label_score_median": 0.024, "label_score_max": 0.09,
                  "state_median": 0.028, "state_max": 0.2,
                  "latents_median": 0.08, "rope_keys_median": 0.07}
