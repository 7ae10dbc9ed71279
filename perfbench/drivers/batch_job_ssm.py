"""Driver ``batch_job_ssm``: ``batch_job``'s back-to-back jobs with the
state-space hybrid (Mamba-2 + grouped-query attention) zero-shot decoder's
set-up in place of the encoder's.

``run`` and the job loop are ``batch_job``'s own (imported, not copied), and
so is ``setup`` but for the model's part: the backend is built through
``get_backend``, its widths are checked against the configuration file key
by key (the experts held and the vocabulary's slice among them), the
corpus's first batch goes through the timed path (``prepare`` / ``transfer``
/ ``launch`` / ``collect``), a seeded sample of its rows is compared with
``reference/granite_hybrid_f32.py`` at the published widths, the timed shapes
and the same share (the experts the step chose, ties apart; the three label
scores; every Mamba-2 layer's state and convolution tail after the prompt
and the attention layer's keys and values; labels where the reference's
margin exceeds the tolerance), and one whole job runs outside the window.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict

import numpy as np

import corpus
from drivers import batch_job

run = batch_job.run

# configuration-file key -> the backend's LlamaConfig field
_WIDTHS = {
    "hidden_size": "dim", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "moe_hidden_dim", "vocab_size": "vocab_size",
    "mamba_n_heads": "mamba_n_heads", "mamba_d_head": "mamba_head_dim",
    "mamba_d_state": "mamba_d_state", "mamba_d_conv": "mamba_conv_kernel",
    "num_local_experts": "experts_held_count",
    "num_experts_per_tok": "moe_top_k",
    "attention_multiplier": "attention_scale",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling",
    "tie_word_embeddings": "tie_embeddings",
    "rms_norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_seq_len",
}


def setup(cell: Dict[str, Any]) -> Dict[str, Any]:
    # batch_job.setup, its `_setup_sentiment` step being this file's
    encoder_setup = batch_job._setup_sentiment
    batch_job._setup_sentiment = _setup_ssm
    try:
        return batch_job.setup(cell)
    finally:
        batch_job._setup_sentiment = encoder_setup


def _check_widths(backend, config) -> None:
    model, cfg = config["model"], backend.config
    for key, field in _WIDTHS.items():
        if getattr(cfg, field) != config[key]:
            raise SystemExit(
                f"perfbench: the backend's {field} is {getattr(cfg, field)}, "
                f"the configuration file's {key} says {config[key]}")
    stated = {
        "the router's width": (cfg.n_experts,
                               config["published"]["num_local_experts"]),
        "experts_held": (list(cfg.experts_held or ()),
                         model["experts_held"]),
        "layer_types": (list(cfg.layer_types or ()), config["layer_types"]),
        "the shared expert": (
            cfg.n_shared_experts * cfg.moe_hidden_dim,
            config["shared_intermediate_size"]),
        "positions": (cfg.use_rope,
                      config["position_embedding_type"] != "nope"),
        "attn_impl": (cfg.attn_impl, model["attn_impl"]),
        "max_prompt_len": (backend.max_prompt_len, model["max_prompt_len"]),
        "dtype": (cfg.dtype, model["dtype"]),
        "param_dtype": (cfg.param_dtype, model["param_dtype"]),
        "prompt_width_floor": (cfg.prompt_width_floor,
                               model.get("prompt_width_floor", 64)),
    }
    for what, (has, says) in stated.items():
        if has != says:
            raise SystemExit(
                f"perfbench: {what} is {has} in the backend, {says} in the "
                "configuration file")


def _setup_ssm(state, mesh_shape) -> None:
    from music_analyst_tpu.engines.sentiment import get_backend
    from music_analyst_tpu.utils.labels import SUPPORTED_LABELS
    from reference import granite_hybrid_f32 as reference

    config, spans = state["config"], state["spans"]
    model = config["model"]
    if mesh_shape:
        raise SystemExit("perfbench: batch_job_ssm runs one chip")
    t0 = time.monotonic()
    backend = get_backend(model["name"])
    state["setup"]["backend_init_s"] = time.monotonic() - t0
    spans.add("perfbench:backend_init", t0, state["setup"]["backend_init_s"])
    _check_widths(backend, config)
    state["backend"] = backend

    first = [row[3] for row in corpus.read_rows(
        state["csv_path"], limit=state["batch_size"])]
    rng = np.random.default_rng([state["cell"]["seed"], 64])
    sample = np.sort(rng.choice(
        len(first), size=min(model["reference_sample"], len(first)),
        replace=False))
    # the rows whose states ride back with the scores: the sampled ones
    # (values of an argument of the timed program, not another program)
    default_probe = backend.probe_rows
    backend.probe_rows = np.resize(sample, default_probe.shape).astype(
        np.int32)
    with spans.span("perfbench:first_batch"):
        prepared = backend.prepare(first)
        _, prompt_ids, prompt_lens = prepared
        handle = backend.launch(backend.transfer(prepared))
        scores, stats = np.asarray(handle[1], np.float64), handle[2]
        labels = backend.collect(handle)
    backend.probe_rows = default_probe

    # a rehearsal runs the test size, whose limits are the test size's
    tol = (reference.TEST_TOLERANCE if state["cell"]["rehearsal"]
           else reference.TOLERANCE)
    with spans.span("perfbench:reference"):
        lens = np.asarray(prompt_lens)[sample]
        # the experts the timed step ran for these rows: the reference
        # takes them where they are ties and counts the rest as wrong
        prefer = reference.prefer_from_system(
            np.asarray(stats["chosen"])[:, sample],
            np.asarray(stats["chosen_labels"])[:, :, sample], lens)
        judged = reference.label_scores(
            backend.params, config, np.asarray(prompt_ids)[sample], lens,
            backend._label_ids, backend._label_lens, prefer=prefer,
            margin=tol["route_margin"])
        probe = {name: np.asarray(value)[:, :len(sample)]
                 for name, value in stats["probe"].items()}
        kept = reference.compare_kept(judged["kept"], probe, lens)
    want, routing = judged["scores"], judged["routing"]
    diff = np.abs(scores[sample] - want)
    compared, wrong = 0, []
    for i, row in zip(sample, want):
        ranked = np.sort(row)
        if (ranked[-1] - ranked[-2] <= tol["label_margin"]
                or not first[i].strip()):
            continue
        compared += 1
        if labels[i] != SUPPORTED_LABELS[int(np.argmax(row))]:
            wrong.append(int(i))
    with spans.span("perfbench:warmup_job"):
        state["first_counts"] = batch_job._run_sentiment(
            state, os.path.join(state["out_dir"], "warmup", "sentiment"))["counts"]
    within = {name: kept[name] <= tol[name]
              for name in reference.KEPT_LIMITS}
    state["checks"]["reference"] = {
        "rows": int(len(sample)), "width": int(np.asarray(prompt_ids).shape[1]),
        "tolerance": tol,
        "max_abs_diff": float(diff.max()),
        "median_abs_diff": float(np.median(diff)),
        "choices_compared": routing["compared"],
        "choices_differ": routing["differ"],
        "choices_wrong": routing["wrong"],
        "deepest_tie": routing["deepest_tie"],
        "deepest": routing["deepest"],
        **kept,
        "labels_compared": compared, "labels_wrong": wrong,
        "ok": bool(diff.max() <= tol["label_score_max"]
                   and np.median(diff) <= tol["label_score_median"]
                   and routing["wrong"] <= tol["wrong_choices"]
                   and all(within.values()) and not wrong),
    }
