"""Plain float32 forward of a ``granitemoehybrid`` decoder (Granite 4.0-H):
the reference of the ``granite-4.0-h-small`` configuration.

Written from the published descriptions: Mamba-2 / SSD (arXiv:2405.21060)
for the state-space layer, and for the order of operations the modelling
code ``transformers.models.granitemoehybrid`` (``GraniteMoeHybridMambaLayer.
torch_forward``, ``GraniteMoeHybridTopKGating``, ``GraniteMoeHybridMoE``,
``GraniteMoeHybridDecoderLayer``, ``GraniteMoeHybridForCausalLM``), against
which a test holds this file at a tiny size.  With ``h`` the RMS-normed input
of a sub-layer (eps from the configuration), ``m`` = ``residual_multiplier``::

    x_0 = embedding_multiplier * E[ids]
    x <- x + m * Mixer_i(RMSNorm(x));  x <- x + m * (Routed(h) + Shared(h))
    logits = (RMSNorm(x) E^T) / logits_scaling          (tied embeddings)

``Mixer_i`` is attention where ``layer_types[i] == "attention"``, else
Mamba-2.

* **Mamba-2** (``H`` heads of ``P`` channels, inner width ``I = H P``, state
  ``N``, one group): ``[z | xBC] = W_in h`` (``I | I + 2N``), ``dt = W_dt h
  [H]`` (the source's one matrix ``[z | xBC | dt]``, split at column ``2I +
  2N``); ``xBC <- SiLU(conv(xBC) + b)``, a depthwise causal convolution of
  ``mamba_d_conv`` taps over time (inputs before a row's first token are
  zero); split ``x [H, P]``, ``B [N]``, ``C [N]``; ``delta = softplus(dt +
  dt_bias)`` (no clamp), ``A = -exp(A_log)``.  Then, a head, with ``S [P, N]``
  zero before the first token, **token by token**: ``S <- exp(delta_t A) S +
  delta_t x_t B_t^T``; ``y_t = S C_t + D x_t``.  ``y <- RMSNorm_I(y * SiLU(z))
  * w`` (the gate first, one norm over all ``I``); ``out = W_out y``.
* **Attention**: ``q, k, v = W_q h, W_k h, W_v h`` (``H_q | H_kv | H_kv``
  heads of ``d``, no bias, **no rotary**: ``position_embedding_type: nope``),
  query head ``i`` reads key head ``i // (H_q / H_kv)``, causal softmax of
  ``q k^T * attention_multiplier`` in float32, ``W_o``.
* **Router**: ``l = W_r h`` over all ``num_local_experts`` (the published
  count: the router's own width); the ``num_experts_per_tok`` largest ``l``;
  weights = softmax over those logits.
* **Experts, the share**: ``share = (first, count)`` names the experts this
  chip holds.  Every HELD expert (``W_out,e (SiLU(a) * b)``, ``[a | b] =
  W_in,e h``: gate then up) runs on every token and a dense ``[tokens,
  count]`` weight matrix (the router's weights at the chosen experts that are
  held, zeros elsewhere) combines them; plus the shared SwiGLU, which every
  share computes alike.  What the absent experts would add is left out and
  that partial sum goes on to the next layer, as in the program (one chip of
  the two that share a layer, without the exchange).  ``share = (0,
  num_local_experts)`` is the uncut layer.
* The vocabulary is the slice the configuration holds: the embedding's rows,
  and so logits, ``log_softmax`` and label scores, are over ``vocab_size``
  ids.

Label scores as ``reference/deepseek_v3_f32.py`` computes them: for each
label one full forward over ``prompt + label`` tokens.  The same forward also
gives what the program keeps after a prompt: every Mamba-2 layer's state
after the prompt's last token (a snapshot taken inside the token loop) and
its convolution's last ``mamba_d_conv - 1`` inputs there, and the attention
layers' keys and values at the prompt's positions.

No model code of the repository is imported (``reference/deepseek_v3_f32``
gives the primitives the references share: the fake-int8 matmul, RMSNorm,
SwiGLU, the held experts one at a time); the weights are read from the backend's parameter tree by name and
upcast inside each layer's program, one layer at a time and one expert at a
time within it.  Matrix multiplications run at ``highest`` precision.

Departures from the source, none of which changes a value unless listed
under ``assumed`` in the configuration file:

* the source's fused ``in_proj`` is two matrices here (``in_proj`` = its ``z |
  xBC`` columns, ``dt_proj`` = its ``dt`` columns), and an expert's fused
  ``input_linear`` two (``gate_experts``, ``up_experts``): the parameter
  tree's names;
* the source takes the softmax over the chosen logits; the program takes it
  over all logits and renormalises the chosen ones: equal
  (``tests/test_granite_hybrid.py`` holds both to
  ``GraniteMoeHybridTopKGating``);
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
    routed_experts,
    swiglu,
)


# --------------------------------------------------------------- Mamba-2

def selective_scan(x, delta, a, b, c, snapshot_at=None):
    """The recurrence, a token a step.  ``x [R, T, H, P]``, ``delta [R, T,
    H]``, ``a [H]``, ``b, c [R, T, N]``; returns ``(y [R, T, H, P], S [R, H,
    P, N])``: ``S`` after the last token, or after token ``snapshot_at[r]``
    of row ``r``."""
    rows, _, heads, width = x.shape

    def step(carry, token):
        s, kept = carry
        t, x_t, d_t, b_t, c_t = token
        s = s * jnp.exp(d_t * a)[..., None, None] + (
            (x_t * d_t[..., None])[..., None] * b_t[:, None, None, :])
        if snapshot_at is not None:
            kept = jnp.where((snapshot_at == t)[:, None, None, None], s, kept)
        return (s, kept), jnp.einsum("rhpn,rn->rhp", s, c_t)

    zero = jnp.zeros((rows, heads, width, b.shape[-1]), F32)
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
    """Mamba-2 over ``h [R, T, D]``; returns ``(out [R, T, D], state [R, H,
    P, N], tail [R, K-1, I + 2N])``: the state and the convolution's last
    inputs after token ``snapshot_at[r]`` (the last without)."""
    rows, n_tok, _ = h.shape
    heads, width, n = (hf["mamba_n_heads"], hf["mamba_d_head"],
                       hf["mamba_d_state"])
    inner, keep = heads * width, hf["mamba_d_conv"] - 1
    both = _mm(h, p["in_proj"], variant)
    gate, before = both[..., :inner], both[..., inner:]
    mixed = jax.nn.silu(short_conv(before, p["conv"], p["conv_bias"]))
    x = mixed[..., :inner].reshape(rows, n_tok, heads, width)
    b, c = mixed[..., inner:inner + n], mixed[..., inner + n:]
    delta = jax.nn.softplus(_mm(h, p["dt_proj"], variant)
                            + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["A_log"].astype(F32))
    y, state = selective_scan(x, delta, a, b, c, snapshot_at)
    y = y + p["D"].astype(F32)[:, None] * x
    y = y.reshape(rows, n_tok, inner) * jax.nn.silu(gate)
    y = rms_norm(y, p["norm"].astype(F32), hf["rms_norm_eps"])
    last = (jnp.full((rows,), n_tok - 1) if snapshot_at is None
            else snapshot_at)
    at = last[:, None] - keep + 1 + jnp.arange(keep)[None, :]       # [R, K-1]
    tail = jnp.where(
        (at >= 0)[..., None],
        jnp.take_along_axis(before, jnp.maximum(at, 0)[..., None], axis=1),
        0.0)
    return _mm(y, p["out_proj"], variant), state, tail


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
                        ) * hf["attention_multiplier"]
    causal = jnp.arange(n_tok)[None, :] <= jnp.arange(n_tok)[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    out = jnp.einsum("rhqk,rkhd->rqhd", jax.nn.softmax(scores, -1),
                     jnp.repeat(v, group, 2))
    return _mm(out.reshape(rows, n_tok, heads * d),
               p["o_proj"]["kernel"].reshape(heads * d, dim), variant), k, v


# ---------------------------------------------------------------- experts

def route(p, h, hf: Dict, share, prefer=None, margin: float = 0.0):
    """``(chosen [.., k], combine [.., count], ties)``: ``combine`` is the
    dense weight matrix over the HELD experts, zeros off the chosen.

    ``prefer [.., k]`` is another implementation's choice for the same
    tokens (``-1`` where it states none).  Where it differs from this
    router's and every expert it names has a logit within ``margin`` of this
    router's k-th, the two are a tie that rounding broke the other way: the
    preferred experts are taken, so that what follows compares arithmetic
    and not two sides of a coin.  A preferred expert further down is a wrong
    choice: this router's own stands and the token is counted.  ``ties``
    holds, per token, ``differs``, ``wrong`` and ``depth`` (how far under the
    k-th logit the lowest preferred expert lies; 0 where the choices
    agree)."""
    logits = h.astype(F32) @ p["router"].astype(F32)
    top, chosen = jax.lax.top_k(logits, hf["num_experts_per_tok"])
    ties = None
    if prefer is not None:
        stated = prefer[..., :1] >= 0
        prefer = jnp.where(stated, prefer, chosen).astype(chosen.dtype)
        differs = (jnp.sort(prefer, -1) != jnp.sort(chosen, -1)).any(-1)
        depth = top[..., -1] - jnp.take_along_axis(logits, prefer, -1).min(-1)
        depth = jnp.where(differs, depth, 0.0)
        wrong = differs & (depth > margin)
        chosen = jnp.where((differs & ~wrong)[..., None], prefer, chosen)
        ties = {"differs": differs, "wrong": wrong, "depth": depth}
    weights = jax.nn.softmax(jnp.take_along_axis(logits, chosen, -1), -1)
    first, count = share
    combine = jnp.sum(
        jax.nn.one_hot(chosen - first, count, dtype=F32)  # absent: no column
        * weights[..., None], axis=-2)
    return chosen, combine, ties


def moe_ffn(p, h, hf: Dict, share, variant: str = "f32", prefer=None,
            margin: float = 0.0, shared: bool = True):
    """This share's part of the layer's feed-forward half: the held
    experts' weighted sum and (``shared``) the shared SwiGLU, which every
    share computes alike and the layer counts once."""
    chosen, combine, ties = route(p, h, hf, share, prefer, margin)
    out = routed_experts(p, h, combine, variant)
    if shared:
        out = out + swiglu(p["shared_experts"], h, variant)
    return out, chosen, ties


# ------------------------------------------------------------------ model

def share_of(hf: Dict):
    """``(first, count)``: the experts this configuration's chip holds."""
    held = (hf.get("model") or {}).get("experts_held")
    return tuple(held) if held else (0, hf["num_local_experts"])


@functools.partial(jax.jit, static_argnames=(
    "hf_items", "mixer", "share", "variant", "margin"))
def _layer(p, x, snapshot_at, prefer, hf_items, mixer: str, share,
           variant: str, margin: float):
    hf = dict(hf_items)
    eps, scale = hf["rms_norm_eps"], hf["residual_multiplier"]
    h = rms_norm(x, p["attention_norm"]["scale"], eps)
    if mixer == "mamba":
        mixed, state, tail = mamba_mixer(p["attention"], h, hf, variant,
                                         snapshot_at)
        kept = {"state": state, "conv": tail}
    else:
        mixed, keys, values = attention(p["attention"], h, hf, variant)
        kept = {"keys": keys, "values": values}
    x = x + scale * mixed
    h = rms_norm(x, p["ffn_norm"]["scale"], eps)
    out, chosen, ties = moe_ffn(p["feed_forward_moe"], h, hf, share, variant,
                                prefer, margin)
    return x + scale * out, kept, chosen, ties


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "variant"))
def _head(norm, embedding, x, read_at, eps: float, scaling: float,
          variant: str):
    """Logits ``[R, P, V]`` at the positions ``read_at [R, P]``: the tied
    head over the vocabulary's slice."""
    x = jnp.take_along_axis(x, read_at[..., None], axis=1)
    return _mm(rms_norm(x, norm["scale"], eps), embedding.T, variant
               ) / scaling


def forward(params, hf: Dict, token_ids, read_at, snapshot_at=None,
            variant: str = "f32", rows_block: int = 4, prefer=None,
            margin: float = 0.0):
    """Logits at ``read_at [R, P]`` of the causal forward over ``token_ids
    [R, T]``; the layers' choices ``[layers, R, T, k]``; with ``prefer`` the
    per-token tie record of every layer; and ``kept``: every Mamba-2 layer's
    ``state [mamba layers, R, H, P, N]`` and ``conv [mamba layers, R, K-1,
    I + 2N]`` after token ``snapshot_at[r]`` (the last without) and every
    attention layer's ``keys`` / ``values [attention layers, R, T, H_kv,
    d]``.  Rows go through in blocks of ``rows_block``; every layer is its
    own program."""
    token_ids = np.asarray(token_ids, np.int32)
    read_at = np.asarray(read_at, np.int32)
    hf_items = _hashable(hf)
    kinds, share = hf["layer_types"], share_of(hf)
    embedding = params["tok_embeddings"]["embedding"]
    logits, choices, ties = [], [], []
    kept = {"state": [], "conv": [], "keys": [], "values": []}
    with jax.default_matmul_precision("highest"):
        for lo in range(0, token_ids.shape[0], rows_block):
            ids = jnp.asarray(token_ids[lo:lo + rows_block])
            snap = (None if snapshot_at is None else jnp.asarray(
                np.asarray(snapshot_at)[lo:lo + rows_block], jnp.int32))
            x = embedding[ids].astype(F32) * hf["embedding_multiplier"]
            chosen_block, ties_block = [], []
            kept_block = {name: [] for name in kept}
            for i, mixer in enumerate(kinds):
                want = None
                if prefer is not None:
                    want = jnp.asarray(prefer[i, lo:lo + rows_block],
                                       jnp.int32)
                x, held, chosen, tie = _layer(
                    params[f"layer_{i}"], x, snap, want, hf_items, mixer,
                    share, variant, margin)
                for name, value in held.items():
                    kept_block[name].append(np.asarray(value))
                chosen_block.append(np.asarray(chosen))
                if tie is not None:
                    ties_block.append(
                        {k: np.asarray(v) for k, v in tie.items()})
            logits.append(np.asarray(_head(
                params["norm"], embedding, x,
                jnp.asarray(read_at[lo:lo + rows_block]),
                hf["rms_norm_eps"], float(hf["logits_scaling"]), variant)))
            for name, values in kept_block.items():
                if values:
                    kept[name].append(np.stack(values))
            choices.append(np.stack(chosen_block))
            if ties_block:
                ties.append({k: np.stack([t[k] for t in ties_block])
                             for k in ties_block[0]})
    out = {"logits": np.concatenate(logits),
           "chosen": np.concatenate(choices, axis=1), "ties": None,
           "kept": {name: np.concatenate(blocks, axis=1)
                    for name, blocks in kept.items() if blocks}}
    if ties:
        out["ties"] = {k: np.concatenate([t[k] for t in ties], axis=1)
                       for k in ties[0]}
    return out


def label_scores(params, hf: Dict, prompt_ids, prompt_lens, label_ids,
                 label_lens, variant: str = "f32", rows_block: int = 4,
                 prefer=None, margin: float = 0.0) -> Dict[str, Any]:
    """The program's label scores from full forwards: ``scores [R,
    labels]`` (mean log-probability of each label's tokens after the
    prompt), ``kept`` (from the first label's forward: what the Mamba-2
    layers hold after the prompt's last token and the attention layers'
    keys and values, whose prompt positions do not depend on the label),
    ``chosen`` (a list, one ``[layers, R, W + L, k]`` a label, ``-1`` on the
    padding) and, with ``prefer``, ``routing``: token-layers compared, how
    many differed, how many of those were wrong (not ties within
    ``margin``), the deepest tie seen and how many lay deeper than each of
    ``DEPTHS``."""
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


def _relative(got, want, axes):
    """Largest absolute difference over the largest absolute entry, over
    ``axes``."""
    return np.abs(got - want).max(axis=axes) / np.maximum(
        np.abs(want).max(axis=axes), 1e-12)


def compare_kept(kept, probe, prompt_lens) -> Dict[str, float]:
    """What the program kept after the prompts (``probe``: ``state [mamba
    layers, R, H, P, N]``, ``conv [mamba layers, R, K-1, W]``, ``keys`` /
    ``values [attention layers, R, S, H_kv, d]``) against this reference's,
    a number a kind: for the states the median and the largest, over (layer,
    row, head), of a head's largest absolute difference over the head's
    largest absolute entry; for the convolution tails the same over (layer,
    row); for keys and values over (layer, row) on the prompt's positions."""
    out = {}
    err = _relative(np.asarray(probe["state"], np.float64), kept["state"],
                    (-1, -2))
    out["state_median"], out["state_max"] = (
        float(np.median(err)), float(err.max()))
    err = _relative(np.asarray(probe["conv"], np.float64), kept["conv"],
                    (-1, -2))
    out["conv_median"], out["conv_max"] = (
        float(np.median(err)), float(err.max()))
    lens = np.asarray(prompt_lens)
    for name in ("keys", "values"):
        got = np.asarray(probe[name], np.float64)
        errs = [_relative(got[layer, r, :n], kept[name][layer, r, :n], None)
                for layer in range(got.shape[0]) for r, n in enumerate(lens)]
        out[f"{name}_median"] = float(np.median(errs))
        out[f"{name}_max"] = float(np.max(errs))
    return out


# The names ``compare_kept`` returns that a limit of ``TOLERANCE`` bounds.
KEPT_LIMITS = ("state_median", "state_max", "conv_median", "keys_median",
               "values_median")


# ------------------------------------------------------------ tolerances
#
# The system computes the same mathematics in bfloat16 (float32 states,
# decays, softmax, router and combination; bfloat16 MXU operands in the SSD
# kernel); the weights are the same bfloat16 values on both sides, so what
# differs is the rounding of activations.  Through the router that rounding
# also breaks ties (which 10 of 72), and with random weights another expert is
# another function.  So, as in ``deepseek_v3_f32.py``, the comparison is made
# in parts, none hidden in another:
#
# * the choices.  The system hands over the experts every compared token ran
#   (``prefer``); where they differ from this reference's and lie within
#   ``route_margin`` of its k-th LOGIT it is a tie and the reference takes the
#   system's experts; deeper is a wrong choice and ``wrong_choices`` allows
#   none.
# * what the prefill leaves behind, which is where the precision shows:
#   every Mamba-2 layer's float32 state after the prompt's last token (a
#   head's largest error over its largest entry) and its convolution's tail,
#   and the attention layer's keys and values on the prompt's positions (a
#   row's largest error over its largest entry).  ``state_median`` /
#   ``conv_median`` / ``keys_median`` / ``values_median`` lie between the two
#   readings; ``state_max`` is a gross-error limit (a row that read a
#   neighbour's state, a chunk taken twice, a missing decay read 0.5-1).
# * the arithmetic, given equal choices: |difference| of the three label
#   scores (mean log-probabilities over the vocabulary's slice).  Under
#   ``logits_scaling`` 16 the logits of a random model are a sixteenth of
#   their size and the three scores lie within a few hundredths of each other
#   and of ``-log(vocabulary)``: a rounding moves them little in either
#   precision: at the test size the two readings overlap, and there
#   ``label_score_median`` and ``label_score_max`` are gross-error limits (a
#   wrong multiplier, a head read at the wrong position) while the precision
#   is judged on the states, tails, keys and values above; at the published
#   widths the readings lie five times apart and the limits between them.
#
# ``label_margin``: labels are compared only where the reference's best label
# beats its second by more than this, twice ``label_score_max`` (two scores
# may each be off).
#
# Readings at the published widths on the chip (my chip runs, PR 37: 8 rows x
# 3 labels of a 32 x 1,024 step, two corpora of ``tools/ssm_reference_probe``
# and the set-up of the cell's runs; 66,090 and 81,930 token-layers compared).
# The bfloat16 system: 9.6-9.8% of the token-layers differ, 942-1,310 deeper
# than 0.02 logits and 36-45 deeper than 0.05, the deepest 0.074 / 0.089;
# scores median 0.0006 / 0.0008, largest 0.0015 / 0.0022; states median 0.0159
# / 0.0164, largest 0.077 / 0.087; convolution tails median 0.0105; keys
# 0.0120 / 0.0121; values 0.0121 / 0.0127.  The int8 reference against the
# same steps: 57-58% differ, 22,671 / 27,486 deeper than 0.05, deepest 0.56;
# scores median 0.0037 / 0.0043, largest 0.016; states median 0.127 / 0.130,
# largest 0.64 / 0.76; tails 0.093-0.094; keys 0.102-0.110; values
# 0.106-0.112.  int8 fails every limit but ``label_margin``.  ``route_margin``
# is twice the deepest tie the bfloat16 system showed; every ``_median`` limit
# is near the geometric mean of its two readings; ``label_score_max`` and
# ``state_max`` lie between the largest of each side.
TOLERANCE = {"route_margin": 0.18, "wrong_choices": 0,
             "label_score_median": 0.0018, "label_score_max": 0.006,
             "label_margin": 0.012,
             "state_median": 0.045, "state_max": 0.3,
             "conv_median": 0.03, "keys_median": 0.035,
             "values_median": 0.035}

# The same limits at the test size (granite-tiny on the CPU, the kernels
# under the interpreter: tests/test_granite_hybrid.py), from 3 seeds x 13
# rows at a 512-wide step (compact stream) and at a narrow one (padded rows).
# The bfloat16 system read deepest ties 0.0043-0.0187 (logits), none deeper
# than 0.02; scores median 0.0001-0.0002 and largest 0.0005-0.0007; states
# median 0.0060-0.0071 and largest 0.020-0.024; convolution tails median
# 0.0039-0.0042; keys 0.0045-0.0051; values 0.0050-0.0052.  The int8
# reference: 0-12 choices deeper than 0.02, none deeper than 0.05; scores
# median 0.0002-0.0004 and largest 0.0008-0.0014 (overlapping: see above);
# states median 0.0151-0.0164 and largest 0.035-0.048; tails 0.0100-0.0111;
# keys 0.0097-0.0105; values 0.0108-0.0113: it fails ``state_median``,
# ``conv_median``, ``keys_median`` and ``values_median``.
TEST_TOLERANCE = {"route_margin": 0.04, "wrong_choices": 0,
                  "label_score_median": 0.001, "label_score_max": 0.003,
                  "label_margin": 0.006,
                  "state_median": 0.0105, "state_max": 0.1,
                  "conv_median": 0.007, "keys_median": 0.0072,
                  "values_median": 0.0078}
