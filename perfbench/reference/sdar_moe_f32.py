"""Plain float32 forward of a ``model_type: sdar_moe`` decoder and of its
generation by diffusion over blocks: the reference of the
``sdar-30b-a3b-chat`` configuration.

Written from the published description (the Hugging Face ``SDARMoeForCausalLM``
/ Qwen3-MoE layout for the order of operations, SDAR's block-diffusion
sampler for the generation).  ``h`` is a position's hidden state, ``D`` the
hidden size:

* Attention: ``a = RMSNorm(h)``; ``q = W_q a`` as ``H`` heads of ``d``, ``k =
  W_k a`` and ``v = W_v a`` as ``H_kv`` heads of ``d`` (``d`` is the
  published ``head_dim``, not ``D / H``), no bias; ``q <- RMSNorm_d(q)``, ``k
  <- RMSNorm_d(k)`` (one learned scale of ``d`` for all heads); RoPE over all
  ``d`` dimensions, half-split (``rotate_half``); query head ``i`` reads key
  head ``i // (H / H_kv)``; scores ``q.k / sqrt(d)``; softmax over the keys
  the mask allows, in float32; ``h <- h + W_o concat(heads)``.
* The mask, block length ``B``: a query at position ``i`` sees key ``j`` iff
  ``j // B <= i // B`` (bidirectional inside a block, causal across).
* Experts, every layer: ``m = RMSNorm(h)``; ``p = softmax(W_r m)`` over all
  experts; the ``top_k`` largest; ``w = p_top / sum(p_top)``
  (``norm_topk_prob``); ``h <- h + sum_e w_e W_down,e (silu(W_gate,e m) *
  W_up,e m)``.  Every expert runs on every token, one expert at a time, and a
  dense ``[tokens, E]`` weight matrix (zeros off the chosen) combines them.
* Final RMSNorm, untied head.
* Generation (greedy): the prompt's ``len // B`` whole blocks are the
  context; its ``len % B`` last tokens open the first generated block
  already clean; a block starts ``[clean..., MASK...]``; a denoising pass
  reads the block over the context, takes at each masked position the argmax
  and, as its confidence, the softmax probability of it, and unmasks every
  masked position whose confidence exceeds ``confidence_threshold`` and at
  least ``B / denoising_steps`` of them, the most confident; a clean block
  joins the context.

No model code of the repository is imported; the weights are read from the
backend's parameter tree by name and upcast from bfloat16 inside each layer's
program (one expert at a time within it), so the reference fits beside the
resident model.  No cache, no kernel, no batching: a row at a time, full
``[heads, T, T]`` scores.  Matrix multiplications run at ``highest``
precision.

Departures from the published code, none of which changes a value:

* **The passes of a row share one forward.**  Every pass is a full forward of
  ``[the prompt's whole blocks, the committed blocks, the current block]``
  under the mask; under that mask nothing a pass reads depends on a later
  block, so the sequence handed to the layers is ``[prompt's whole blocks |
  block 0 at pass 0 | block 0 at pass 1 | ... | block 0 clean | block 1 at
  pass 0 | ...]``, each copy of a block at the block's own positions and
  seeing exactly the prompt's whole blocks, the CLEAN copies of the blocks
  before it, and itself.  One forward of 1,024 + 80 positions a row then
  gives what 17 forwards of up to 1,040 would, value for value (the tests
  hold it against the one-pass-one-forward form).
* The router's softmax is over all experts before the top-k
  (``norm_topk_prob`` renormalises), as published; no capacity, no drop.

It is handed the system's choices where they are ties, as
``deepseek_v3_f32.py`` is handed the experts: the experts chosen (within
``route_margin`` of its k-th probability), the token unmasked (within
``token_margin`` of its own best logit at that position) and the position
unmasked (its log-confidence within ``confidence_margin`` of its own most
confident masked position's, or of the threshold); a deeper difference is a
wrong choice and none is allowed.  Given equal choices it compares
**logits**, as the log-probability of each unmasked token at the pass that
unmasked it, and the keys and values the system's caches hold (the prefill's
for the prompt's whole blocks, the commit passes' for the generated blocks)
against its own for the same positions: "prefill, then decoding through the
cache, agrees with the full forward".

``variant="int8"`` computes the same forward with every projection and
expert matmul fake-quantized (weights per output channel, activations per
row, symmetric int8): the "nearest precision below" reading the tolerances
are set against.  Tolerances (``TOLERANCE``), with their reasons, are at the
bottom.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Mapping

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


def rope_half(x, positions, theta: float):
    """``x [T, heads, d]`` rotated by ``positions [T]``, pairs ``(x[i], x[i +
    d/2])`` (``rotate_half``)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    angles = positions.astype(F32)[:, None] * inv_freq          # [T, d/2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


# ---------------------------------------------------------------- layers

def attention(p, h, positions, seen, hf: Mapping, variant: str = "f32"):
    """Grouped-query attention with QK-norm over ``h [T, D]``; ``seen [T,
    T]`` says which key each query reads.  Returns ``(out [T, D], k [T,
    H_kv, d], v [T, H_kv, d])``: the keys as attention reads them (normed
    and rotated)."""
    n_tok, dim = h.shape
    heads, kv_heads = hf["num_attention_heads"], hf["num_key_value_heads"]
    d, eps = hf["head_dim"], hf["rms_norm_eps"]
    q = _mm(h, p["q_proj"]["kernel"].reshape(dim, heads * d),
            variant).reshape(n_tok, heads, d)
    k = _mm(h, p["k_proj"]["kernel"].reshape(dim, kv_heads * d),
            variant).reshape(n_tok, kv_heads, d)
    v = _mm(h, p["v_proj"]["kernel"].reshape(dim, kv_heads * d),
            variant).reshape(n_tok, kv_heads, d)
    q = rope_half(rms_norm(q, p["q_norm"]["scale"], eps), positions,
                  hf["rope_theta"])
    k = rope_half(rms_norm(k, p["k_norm"]["scale"], eps), positions,
                  hf["rope_theta"])
    group = heads // kv_heads
    scores = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, group, axis=1))
    scores = jnp.where(seen[None], scores / np.sqrt(d), -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1),
                     jnp.repeat(v, group, axis=1))
    return _mm(out.reshape(n_tok, heads * d),
               p["o_proj"]["kernel"].reshape(heads * d, dim), variant), k, v


def route(p, h, hf: Mapping, prefer=None, margin: float = 0.0):
    """``(chosen [T, k], combine [T, E], ties)``: ``combine`` is the dense
    weight matrix, zeros off the chosen experts.

    ``prefer [T, k]`` is another implementation's choice for the same
    tokens (``-1`` where it states none).  Where it differs from this
    router's and every expert it names has a probability within ``margin``
    of this router's k-th, the two are a tie that rounding broke the other
    way: the preferred experts are taken.  A preferred expert further down
    is a wrong choice: this router's own stands and the token is counted.
    ``ties`` holds, per token, ``differs``, ``wrong`` and ``depth``."""
    probs = jax.nn.softmax(h.astype(F32) @ p["router"].astype(F32), -1)
    k = hf["num_experts_per_tok"]
    top, chosen = jax.lax.top_k(probs, k)
    ties = None
    if prefer is not None:
        stated = prefer[..., :1] >= 0
        prefer = jnp.where(stated, prefer, chosen).astype(chosen.dtype)
        differs = (jnp.sort(prefer, -1) != jnp.sort(chosen, -1)).any(-1)
        depth = top[..., -1] - jnp.take_along_axis(probs, prefer, -1).min(-1)
        depth = jnp.where(differs, depth, 0.0)
        wrong = differs & (depth > margin)
        chosen = jnp.where((differs & ~wrong)[..., None], prefer, chosen)
        ties = {"differs": differs, "wrong": wrong, "depth": depth}
    weights = jnp.take_along_axis(probs, chosen, -1)
    if hf["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)
    combine = jnp.sum(
        jax.nn.one_hot(chosen, probs.shape[-1], dtype=F32)
        * weights[..., None], axis=-2)
    return chosen, combine, ties


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


@functools.partial(jax.jit, static_argnames=("hf_items", "variant", "margin"))
def _layer(p, x, positions, seen, prefer, hf_items, variant: str,
           margin: float):
    hf = dict(hf_items)
    eps = hf["rms_norm_eps"]
    out, k, v = attention(p["attention"],
                          rms_norm(x, p["attention_norm"]["scale"], eps),
                          positions, seen, hf, variant)
    x = x + out
    h = rms_norm(x, p["ffn_norm"]["scale"], eps)
    chosen, combine, ties = route(p["feed_forward_moe"], h, hf, prefer,
                                  margin)
    return (x + routed_experts(p["feed_forward_moe"], h, combine, variant),
            k, v, chosen, ties)


@functools.partial(jax.jit, static_argnames=("eps", "variant"))
def _head(norm, lm_head, x, eps: float, variant: str):
    return _mm(rms_norm(x, norm["scale"], eps), lm_head["kernel"], variant)


def _hashable(hf: Mapping):
    return tuple(sorted((k, v) for k, v in hf.items()
                        if isinstance(v, (int, float, bool, str))))


def forward(params, hf: Mapping, token_ids, positions, seen, read_at,
            variant: str = "f32", prefer=None, margin: float = 0.0):
    """One row through the layers: ``token_ids [T]`` at ``positions [T]``
    under ``seen [T, T]``.  Returns ``logits [len(read_at), V]`` at the
    indices ``read_at``, every layer's ``keys`` / ``values [layers, T, H_kv,
    d]``, the routers' ``chosen [layers, T, k]`` and, with ``prefer``
    (another implementation's choices in that layout, ``-1`` = none stated),
    ``ties {differs, wrong, depth: [layers, T]}``.  Every layer is its own
    program, so one layer's float32 temporaries are live at a time."""
    hf_items = _hashable(hf)
    ids = jnp.asarray(np.asarray(token_ids, np.int32))
    positions = jnp.asarray(np.asarray(positions, np.int32))
    seen = jnp.asarray(np.asarray(seen, bool))
    keys, values, chosen, ties = [], [], [], []
    with jax.default_matmul_precision("highest"):
        x = params["tok_embeddings"]["embedding"][ids].astype(F32)
        for i in range(hf["num_hidden_layers"]):
            want = (None if prefer is None
                    else jnp.asarray(prefer[i], jnp.int32))
            x, k, v, picked, tie = _layer(
                params[f"layer_{i}"], x, positions, seen, want, hf_items,
                variant, margin)
            keys.append(np.asarray(k))
            values.append(np.asarray(v))
            chosen.append(np.asarray(picked))
            if tie is not None:
                ties.append({name: np.asarray(t) for name, t in tie.items()})
        logits = np.asarray(_head(
            params["norm"], params["lm_head"],
            x[jnp.asarray(np.asarray(read_at, np.int32))],
            hf["rms_norm_eps"], variant))
    return {"logits": logits, "keys": np.stack(keys),
            "values": np.stack(values), "chosen": np.stack(chosen),
            "ties": ({name: np.stack([t[name] for t in ties])
                      for name in ties[0]} if ties else None)}


def block_causal(n_tok: int, block: int) -> np.ndarray:
    """``seen [T, T]`` of a plain sequence under the block-causal rule."""
    blocks = np.arange(n_tok) // block
    return blocks[None, :] <= blocks[:, None]


# ------------------------------------------------------ one row's passes

def row_layout(prompt_ids, prompt_len: int, row: Mapping, sampler: Mapping,
               width: int):
    """The sequence that holds every pass of one row (module docstring):
    ``ids``, ``positions [T]``, ``seen [T, T]`` and where things are.

    ``row`` is what the system produced for it: ``tokens`` / ``fresh`` /
    ``unmask_pass [G, n]``.  ``T = width + G * (steps + 1) * n`` whatever
    the row's length and passes (one compiled shape): the prompt takes the
    first ``width`` slots (those at or behind its whole blocks are seen by
    nobody), block ``g``'s copy ``c`` (``c < steps``: on entry to pass
    ``c``; ``c = steps``: clean) the ``n`` slots from :func:`copy_slot`;
    copies of passes the row did not need see themselves alone and are
    never read."""
    n, steps = sampler["block_length"], sampler["denoising_steps"]
    tokens = np.asarray(row["tokens"], np.int64)
    fresh = np.asarray(row["fresh"], bool)
    pass_of = np.asarray(row["unmask_pass"], np.int64)
    blocks = tokens.shape[0]
    whole = prompt_len // n * n
    total = width + blocks * (steps + 1) * n
    ids = np.zeros(total, np.int64)
    ids[:width] = np.asarray(prompt_ids)[:width]
    positions = np.arange(total)
    seen = np.eye(total, dtype=bool)
    seen[:whole, :whole] = block_causal(whole, n)
    passes = []  # (block, pass) pairs the row ran
    for g in range(blocks):
        n_passes = int(pass_of[g].max()) + 1  # 0 where the prompt filled it
        for c in range(steps + 1):
            lo = copy_slot(width, g, c, sampler)
            at = slice(lo, lo + n)
            positions[at] = whole + g * n + np.arange(n)
            if c < steps and c >= n_passes:
                continue
            clean = c == steps
            still_masked = fresh[g] & (pass_of[g] >= c)
            ids[at] = tokens[g] if clean else np.where(
                still_masked, sampler["mask_token_id"], tokens[g])
            seen[at, :whole] = True
            for before in range(g):
                lo_b = copy_slot(width, before, steps, sampler)
                seen[at, lo_b:lo_b + n] = True
            seen[at, at] = True
            if not clean:
                passes.append((g, c))
    return {"ids": ids, "positions": positions, "seen": seen,
            "whole": whole, "passes": passes}


def copy_slot(width: int, block: int, copy: int, sampler: Mapping) -> int:
    n, steps = sampler["block_length"], sampler["denoising_steps"]
    return width + (block * (steps + 1) + copy) * n


def row_prefer(layout, chosen, chosen_denoise, chosen_commit,
               sampler: Mapping, width: int):
    """The experts the system ran, in :func:`row_layout`'s order: ``[layers,
    T, k]`` from the prefill's ``chosen [layers, W, k]`` (the prompt's whole
    blocks), ``chosen_denoise [G, steps, layers, n, k]`` and ``chosen_commit
    [G, layers, n, k]``; ``-1`` where the system states none."""
    n, steps = sampler["block_length"], sampler["denoising_steps"]
    chosen = np.asarray(chosen, np.int32)
    layers, k = chosen.shape[0], chosen.shape[-1]
    prefer = np.full((layers, len(layout["ids"]), k), -1, np.int32)
    prefer[:, :layout["whole"]] = chosen[:, :layout["whole"]]
    for g, c in layout["passes"]:
        lo = copy_slot(width, g, c, sampler)
        prefer[:, lo:lo + n] = np.asarray(chosen_denoise)[g, c]
    for g in range(np.asarray(chosen_commit).shape[0]):
        lo = copy_slot(width, g, steps, sampler)
        prefer[:, lo:lo + n] = np.asarray(chosen_commit)[g]
    return prefer


def judge_row(params, hf: Mapping, sampler: Mapping, prompt_ids,
              prompt_len: int, row: Mapping, variant: str = "f32",
              tolerance: Mapping | None = None) -> Dict[str, Any]:
    """Everything the system did for one row, against one full forward.

    ``row``: ``tokens`` / ``fresh`` / ``unmask_pass`` / ``token_logp [G,
    n]``, ``chosen [layers, W, k]``, ``chosen_denoise [G, steps, layers, n,
    k]``, ``chosen_commit [G, layers, n, k]`` and the row of the system's
    final caches, ``keys`` / ``values [layers, L, H_kv, d]``.  Returns the
    counts and the differences :func:`judge` gathers."""
    tol = TOLERANCE if tolerance is None else tolerance
    n, steps = sampler["block_length"], sampler["denoising_steps"]
    width = np.asarray(row["chosen"]).shape[1]
    layout = row_layout(prompt_ids, prompt_len, row, sampler, width)
    prefer = row_prefer(layout, row["chosen"], row["chosen_denoise"],
                        row["chosen_commit"], sampler, width)
    read_at = np.concatenate(
        [copy_slot(width, g, c, sampler) + np.arange(n)
         for g, c in layout["passes"]] or [np.zeros(0, np.int64)])
    out = forward(params, hf, layout["ids"], layout["positions"],
                  layout["seen"], read_at, variant, prefer,
                  tol["route_margin"])
    whole = layout["whole"]
    tokens = np.asarray(row["tokens"], np.int64)
    fresh = np.asarray(row["fresh"], bool)
    pass_of = np.asarray(row["unmask_pass"], np.int64)
    logits = out["logits"].astype(np.float64).reshape(
        len(layout["passes"]), n, -1)
    logp_all = logits - np.log(np.exp(
        logits - logits.max(-1, keepdims=True)).sum(-1, keepdims=True)) \
        - logits.max(-1, keepdims=True)
    log_threshold = np.log(sampler["confidence_threshold"])
    at_least = n // steps
    result = {"logp_diff": [], "wrong_tokens": 0, "wrong_positions": 0,
              "tokens_compared": 0, "deepest_token_tie": 0.0,
              "deepest_position_tie": 0.0}
    for index, (g, c) in enumerate(layout["passes"]):
        masked = fresh[g] & (pass_of[g] >= c)
        took = fresh[g] & (pass_of[g] == c)
        logp = logp_all[index]                                   # [n, V]
        confidence = logp.max(-1)
        best = np.sort(confidence[masked])[::-1][at_least - 1]
        for j in np.flatnonzero(masked):
            # the position: unmasked iff over the threshold or among the
            # ``at_least`` most confident; how far the system's choice lies
            # from that rule, 0 where the two agree
            over = confidence[j] - log_threshold
            if took[j]:
                depth = max(0.0, min(-over, best - confidence[j]))
            else:
                depth = max(0.0, over,
                            confidence[j] - confidence[took].max())
            if depth <= tol["confidence_margin"]:
                result["deepest_position_tie"] = max(
                    result["deepest_position_tie"], float(depth))
            else:
                result["wrong_positions"] += 1
        for j in np.flatnonzero(took):
            token = tokens[g, j]
            depth = confidence[j] - logp[j, token]  # 0 = its own argmax
            result["tokens_compared"] += 1
            if depth > tol["token_margin"]:
                result["wrong_tokens"] += 1
                continue
            result["deepest_token_tie"] = max(result["deepest_token_tie"],
                                              float(depth))
            result["logp_diff"].append(abs(
                float(np.asarray(row["token_logp"])[g, j])
                - float(logp[j, token])))

    def relative(system, mine):
        """Largest difference of a position's vector over the RMS of the
        reference's, per (layer, position)."""
        system = np.asarray(system, np.float32).reshape(
            system.shape[0], system.shape[1], -1)
        mine = mine.reshape(system.shape)
        rms = np.sqrt((mine ** 2).mean(-1)) + 1e-12
        return (np.abs(system - mine).max(-1) / rms).reshape(-1)

    clean_slots = np.concatenate(
        [copy_slot(width, g, steps, sampler) + np.arange(n)
         for g in range(tokens.shape[0])])
    cached = whole + np.arange(len(clean_slots))
    for name in ("keys", "values"):
        system = np.asarray(row[name], np.float32)
        result[f"prefill_{name}"] = relative(
            system[:, :whole], out[name][:, :whole])
        result[f"commit_{name}"] = relative(
            system[:, cached], out[name][:, clean_slots])
    ties = out["ties"]
    stated = prefer[..., 0] >= 0
    result.update(
        choices_compared=int(stated.sum()),
        choices_differ=int((ties["differs"] & stated).sum()),
        choices_wrong=int((ties["wrong"] & stated).sum()),
        deepest_route_tie=float(np.where(
            ties["wrong"] | ~stated, 0.0, ties["depth"]).max()),
        deepest_route=float(np.where(stated, ties["depth"], 0.0).max()))
    return result


def system_rows(out: Mapping, stats: Mapping, rows) -> Dict[str, Any]:
    """The rows ``rows`` of one step as the program returned it
    (``models/block_diffusion``: ``out`` of the block loop; ``stats`` of the
    prefill with the committed ``caches``), brought to the host: the layout
    :func:`judge` takes, the row axis where the program has it."""
    rows = jnp.asarray(np.asarray(rows, np.int32))

    def take(x, axis):
        return np.asarray(jnp.take(x, rows, axis=axis))

    system = {name: take(out[name], 1) for name in (
        "tokens", "fresh", "unmask_pass", "token_logp")}
    system["chosen"] = take(stats["chosen"], 1)
    system["chosen_commit"] = take(out["chosen_commit"], 2)
    system["chosen_denoise"] = take(out["chosen_denoise"], 3)
    for name in ("keys", "values"):
        system[name] = np.stack([
            np.asarray(jnp.take(getattr(cache, name), rows, axis=0).astype(
                F32)) for cache in stats["caches"]])
    return system


def judge(params, hf: Mapping, sampler: Mapping, prompt_ids, prompt_lens,
          system: Mapping, variant: str = "f32",
          tolerance: Mapping | None = None) -> Dict[str, Any]:
    """The rows of one step of the system (:func:`system_rows`; ``prompt_ids
    [R, W]`` / ``prompt_lens [R]`` the same rows) against this reference.
    Returns the readings, the limits and ``ok``."""
    tol = dict(TOLERANCE if tolerance is None else tolerance)
    prompt_ids = np.asarray(prompt_ids)
    got = []
    for r in range(len(prompt_ids)):
        row = {name: np.asarray(system[name])[:, r]
               for name in ("tokens", "fresh", "unmask_pass", "token_logp",
                            "chosen", "keys", "values")}
        row["chosen_commit"] = np.asarray(system["chosen_commit"])[:, :, r]
        row["chosen_denoise"] = np.asarray(system["chosen_denoise"])[:, :, :, r]
        got.append(judge_row(params, hf, sampler, prompt_ids[r],
                             int(prompt_lens[r]), row, variant, tol))

    def joined(name):
        return np.concatenate([np.asarray(g[name], np.float64).reshape(-1)
                               for g in got])

    logp = joined("logp_diff")
    readings = {
        "rows": len(got),
        "choices_compared": sum(g["choices_compared"] for g in got),
        "choices_differ": sum(g["choices_differ"] for g in got),
        "wrong_choices": sum(g["choices_wrong"] for g in got),
        "deepest_route_tie": max(g["deepest_route_tie"] for g in got),
        "deepest_route": max(g["deepest_route"] for g in got),
        "tokens_compared": sum(g["tokens_compared"] for g in got),
        "wrong_tokens": int(sum(g["wrong_tokens"] for g in got)),
        "wrong_positions": int(sum(g["wrong_positions"] for g in got)),
        "deepest_token_tie": max(g["deepest_token_tie"] for g in got),
        "deepest_position_tie": max(g["deepest_position_tie"] for g in got),
        "logp_median": float(np.median(logp)) if len(logp) else 0.0,
        "logp_max": float(logp.max()) if len(logp) else 0.0,
    }
    for part in ("prefill", "commit"):
        both = np.concatenate([joined(f"{part}_keys"),
                               joined(f"{part}_values")])
        readings[f"{part}_kv_median"] = float(np.median(both))
        readings[f"{part}_kv_max"] = float(both.max())
    limits = ("wrong_choices", "wrong_tokens", "wrong_positions",
              "logp_median", "logp_max", "prefill_kv_median",
              "commit_kv_median", "commit_kv_max")
    readings["failed"] = [name for name in limits
                          if readings[name] > tol[name]]
    readings["ok"] = not readings["failed"]
    readings["tolerance"] = tol
    return readings


# ------------------------------------------------------------ tolerances
#
# The system computes the same mathematics in bfloat16 (float32 softmax,
# router, combination and head); the weights are the same bfloat16 values on
# both sides, so what differs is the rounding of activations, and what that
# rounding decides: which of two experts of nearly equal probability runs,
# which of two tokens of nearly equal logit is the argmax, which of two
# masked positions is the more confident.  With random weights each of those
# is another function downstream, so the comparison is made in two parts,
# neither hidden in the other (the readings behind every number are in
# PERF.md section 4, seed by seed):
#
# * the choices, handed over where they are ties.  ``route_margin``: a
#   preferred expert's probability may lie this far under the reference's
#   k-th (probabilities of 128 experts: about 0.008 each).  ``token_margin``:
#   the system's token may lie this far, in logit units = log-probability,
#   under the reference's argmax at that position.  ``confidence_margin``: the
#   log-confidence of a position the system unmasked may lie this far under
#   the reference's most confident masked position's (or under the
#   threshold's).  Deeper is a wrong choice; ``wrong_choices``,
#   ``wrong_tokens`` and ``wrong_positions`` allow none.
# * the arithmetic, given equal choices.  ``logp_median`` / ``logp_max``:
#   |difference| of the log-probability of each unmasked token at the pass
#   that unmasked it (about -ln(vocabulary) + a few with random weights).
#   ``prefill_kv_median``, ``commit_kv_median``, ``commit_kv_max``: the
#   largest difference of a cached key or value vector over the RMS of the
#   reference's, per (layer, position): the prefill's entries for the
#   prompt's whole blocks, and the entries the commit passes wrote.
#
# Each limit lies between the largest reading the bfloat16 system gave and
# the smallest the int8 reference gave against this one, but for the two
# margins of log-probability ties (below).  Readings at the published widths
# on the chip (my chip runs, PR 31: 8 rows of the first timed step a seed,
# 113-121 tokens and 13,848-23,772 token-layers compared; fourteen bfloat16
# readings, three at 6 layers and eleven at the 7 the configuration holds,
# five int8 readings; the two depths read alike).  Read with generous
# margins (experts 0.01, tokens and positions 1.0: every tie handed over):
#
#                       bfloat16 system        int8 reference
#   deepest expert tie  0.00052-0.00085        0.0037-0.0043
#   deepest token tie   0-0.0165               0.061-0.117
#   deepest position    0.0003-0.0165          0.0275-0.092
#   logp median         0.0035-0.0047          0.0179-0.0249
#   logp largest        0.0138-0.0194          0.066-0.100
#   caches, median      0.0223-0.0233          0.112-0.128
#   committed, largest  0.041-0.054            0.198-0.213
#
# Under margins of 0.0016 / 0.04 / 0.025 (three seeds, 7 layers) the int8
# reference failed every one of the eight limits: 426 / 434 / 235 wrong
# experts, 3 / 1 / 2 wrong tokens, 8 / 6 / 8 wrong positions,
# log-probabilities median 0.0166-0.0215 and largest 0.075-0.135, caches
# median 0.113-0.128, largest committed 0.48-0.73 (a wrong expert is another
# function downstream); the bfloat16 system none.
#
# The margins of ties are set by how the LARGEST tie of a run is
# distributed, because one wrong choice in one run refuses a PR.  A run
# compares about 128 passes of a row; over fourteen runs its deepest
# position tie read 0.0003-0.0165 (mean 0.0084, deviation 0.0044), which as
# an extreme value puts 0.025 at a chance of 0.4% a run, too near for a check
# every later PR repeats fourteen times.  A tie of positions or of tokens is
# two roundings of a log-probability apart (largest single one read:
# 0.0194), so both margins stand at 0.05, beyond twice that: above three of
# the five int8 readings of the deepest position tie.  These two margins
# alone do not tell int8 from bfloat16; the expert margin (2.4 times the
# widest bfloat16 tie, under half the narrowest int8 one) and the five
# arithmetic limits do, each with room on both sides.
TOLERANCE = {"route_margin": 0.0016, "token_margin": 0.05,
             "confidence_margin": 0.05,
             "wrong_choices": 0, "wrong_tokens": 0, "wrong_positions": 0,
             "logp_median": 0.010, "logp_max": 0.04,
             "prefill_kv_median": 0.05, "commit_kv_median": 0.05,
             "commit_kv_max": 0.10}

# The same limits at the test size (sdar-tiny on the CPU, tests/test_sdar.py;
# 8 experts at 2 a token, so a probability is about 0.125 and the margin of
# the experts wider).  Readings, 5 seeds x 8 rows (3 in the rehearsal): the
# bfloat16 system read deepest ties 0.0036-0.026 (experts), 0-0.011 (tokens),
# 0-0.050 (positions), nothing wrong; log-probabilities median 0.0062-0.0081,
# largest 0.028-0.043; caches median 0.0178-0.0204, largest committed
# 0.047-0.075.  The int8 reference: log-probabilities median 0.0207-0.0276,
# largest 0.10-0.35; caches median 0.056-0.062, largest committed 0.17-0.24;
# deepest ties 0.018-0.24 / 0.05-0.12 / 0.035-0.20 (they overlap bfloat16's
# where the margins are this wide: the medians are what int8 breaks).
TEST_TOLERANCE = {"route_margin": 0.04, "token_margin": 0.03,
                  "confidence_margin": 0.15,
                  "wrong_choices": 0, "wrong_tokens": 0, "wrong_positions": 0,
                  "logp_median": 0.014, "logp_max": 0.09,
                  "prefill_kv_median": 0.035, "commit_kv_median": 0.035,
                  "commit_kv_max": 0.13}
