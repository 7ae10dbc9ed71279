"""Driver ``batch_job_decoder``: ``batch_job``'s back-to-back jobs with the
zero-shot decoder's set-up in place of the encoder's.

``run`` and the job loop are ``batch_job``'s own (imported, not copied), and
so is ``setup`` but for the model's part: the backend is built through
``get_backend``, its widths are checked against the configuration file key
by key, the corpus's first batch goes through the timed path (``prepare`` /
``transfer`` / ``launch`` / ``collect``), a seeded sample of its rows is
compared with ``reference/deepseek_v3_f32.py`` at the published widths and
the timed shapes (the experts the step chose, ties apart; label scores;
labels where the reference's margin exceeds the tolerance), and one whole
job runs outside the window.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict

import numpy as np

import common
import corpus
from drivers import batch_job

run = batch_job.run

# configuration-file key -> the backend's LlamaConfig field
_WIDTHS = {
    "hidden_size": "dim", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "hidden_dim", "vocab_size": "vocab_size",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "moe_intermediate_size": "moe_hidden_dim",
    "n_routed_experts": "n_experts", "num_experts_per_tok": "moe_top_k",
    "n_shared_experts": "n_shared_experts",
    "first_k_dense_replace": "first_k_dense_replace",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob", "rope_theta": "rope_theta",
    "rope_interleave": "rope_interleave", "rms_norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_seq_len",
}


def setup(cell: Dict[str, Any]) -> Dict[str, Any]:
    # batch_job.setup, its `_setup_sentiment` step being this file's
    encoder_setup = batch_job._setup_sentiment
    batch_job._setup_sentiment = _setup_decoder
    try:
        return batch_job.setup(cell)
    finally:
        batch_job._setup_sentiment = encoder_setup


def _setup_decoder(state, mesh_shape) -> None:
    from music_analyst_tpu.engines.sentiment import get_backend
    from reference import deepseek_v3_f32 as reference

    config, spans = state["config"], state["spans"]
    model = config["model"]
    if mesh_shape:
        raise SystemExit("perfbench: batch_job_decoder runs one chip")
    t0 = time.monotonic()
    backend = get_backend(model["name"])
    state["setup"]["backend_init_s"] = time.monotonic() - t0
    spans.add("perfbench:backend_init", t0, state["setup"]["backend_init_s"])
    for key, field in _WIDTHS.items():
        if getattr(backend.config, field) != config[key]:
            raise SystemExit(
                f"perfbench: the backend's {field} is "
                f"{getattr(backend.config, field)}, the configuration "
                f"file's {key} says {config[key]}")
    if (backend.max_prompt_len != model["max_prompt_len"]
            or backend.config.dtype != model["dtype"]
            or backend.config.param_dtype != model["param_dtype"]
            or backend.config.prompt_width_floor != model.get(
                "prompt_width_floor", 64)):
        raise SystemExit(
            "perfbench: max_prompt_len, prompt_width_floor or a dtype "
            "differ from the file")
    state["backend"] = backend

    first = [row[3] for row in corpus.read_rows(
        state["csv_path"], limit=state["batch_size"])]
    with spans.span("perfbench:first_batch"):
        prepared = backend.prepare(first)
        _, prompt_ids, prompt_lens = prepared
        handle = backend.launch(backend.transfer(prepared))
        scores, stats = np.asarray(handle[1], np.float64), handle[2]
        labels = backend.collect(handle)

    tol = reference.TOLERANCE
    with spans.span("perfbench:reference"):
        rng = np.random.default_rng([state["cell"]["seed"], 64])
        sample = np.sort(rng.choice(
            len(first), size=min(model["reference_sample"], len(first)),
            replace=False))
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
    want, routing = judged["scores"], judged["routing"]
    diff = np.abs(scores[sample] - want)
    from music_analyst_tpu.utils.labels import SUPPORTED_LABELS

    compared, wrong = 0, []
    for i, row in zip(sample, want):
        ranked = np.sort(row)
        if ranked[-1] - ranked[-2] <= tol["label_margin"] or not first[i].strip():
            continue
        compared += 1
        if labels[i] != SUPPORTED_LABELS[int(np.argmax(row))]:
            wrong.append(int(i))
    with spans.span("perfbench:warmup_job"):
        state["first_counts"] = batch_job._run_sentiment(
            state, os.path.join(state["out_dir"], "warmup", "sentiment"))["counts"]
    state["checks"]["reference"] = {
        "rows": int(len(sample)), "width": int(np.asarray(prompt_ids).shape[1]),
        "tolerance": tol,
        "max_abs_diff": float(diff.max()),
        "median_abs_diff": float(np.median(diff)),
        "choices_compared": routing["compared"],
        "choices_differ": routing["differ"],
        "choices_wrong": routing["wrong"],
        "deepest_tie": routing["deepest_tie"],
        "labels_compared": compared, "labels_wrong": wrong,
        "ok": bool(diff.max() <= tol["label_score_max"]
                   and np.median(diff) <= tol["label_score_median"]
                   and routing["wrong"] <= tol["wrong_choices"]
                   and not wrong),
    }
