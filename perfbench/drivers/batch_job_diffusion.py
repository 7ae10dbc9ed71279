"""Driver ``batch_job_diffusion``: ``batch_job``'s back-to-back jobs with a
block-diffusion decoder's set-up in place of the encoder's.

``run`` and the job loop are ``batch_job``'s own (imported, not copied), and
so is ``setup`` but for the model's part: the backend is built through
``get_backend``, its widths and its sampler are checked against the
configuration file key by key, the corpus's first batch goes through the
timed path (``prepare`` / ``transfer`` / ``launch`` / ``collect``, the
committed caches kept for once), a seeded sample of its rows is compared
with ``reference/sdar_moe_f32.py`` at the published widths and the timed
shapes (the experts every pass chose, the tokens and the positions
unmasked, ties apart; the log-probability of every unmasked token; the keys
and values the prefill and the commit passes left in the caches), and one
whole job runs outside the window.
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
    "head_dim": "head_dim", "vocab_size": "vocab_size",
    "moe_intermediate_size": "moe_hidden_dim", "num_experts": "n_experts",
    "num_experts_per_tok": "moe_top_k", "norm_topk_prob": "norm_topk_prob",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_seq_len",
}
# keys of the file's ``model`` section -> the backend's LlamaConfig field
SAMPLER = ("block_length", "denoising_steps", "confidence_threshold",
           "mask_token_id")
_MODEL = {**{key: key for key in SAMPLER}, "dtype": "dtype",
          "param_dtype": "param_dtype",
          "prompt_width_floor": "prompt_width_floor"}


def setup(cell: Dict[str, Any]) -> Dict[str, Any]:
    # batch_job.setup, its `_setup_sentiment` step being this file's
    encoder_setup = batch_job._setup_sentiment
    batch_job._setup_sentiment = _setup_diffusion
    try:
        return batch_job.setup(cell)
    finally:
        batch_job._setup_sentiment = encoder_setup


def _setup_diffusion(state, mesh_shape) -> None:
    from music_analyst_tpu.engines.sentiment import get_backend
    from reference import sdar_moe_f32 as reference

    config, spans = state["config"], state["spans"]
    model = config["model"]
    if mesh_shape:
        raise SystemExit("perfbench: batch_job_diffusion runs one chip")
    t0 = time.monotonic()
    backend = get_backend(model["name"])
    state["setup"]["backend_init_s"] = time.monotonic() - t0
    spans.add("perfbench:backend_init", t0, state["setup"]["backend_init_s"])
    for said, keys in ((config, _WIDTHS), (model, _MODEL)):
        for key, field in keys.items():
            if getattr(backend.config, field) != said[key]:
                raise SystemExit(
                    f"perfbench: the backend's {field} is "
                    f"{getattr(backend.config, field)}, the configuration "
                    f"file's {key} says {said[key]}")
    if (backend.max_prompt_len != model["max_prompt_len"]
            or backend.config.moe_router != "softmax_topk"
            or not backend.config.qk_norm
            or backend.gen_blocks != model.get("gen_blocks", 4)):
        raise SystemExit(
            "perfbench: max_prompt_len, the router, QK-norm or the blocks "
            "a row differ from the file")
    state["backend"] = backend

    first = [row[3] for row in corpus.read_rows(
        state["csv_path"], limit=state["batch_size"])]
    with spans.span("perfbench:first_batch"):
        prepared = backend.prepare(first)
        _, prompt_ids, prompt_lens = prepared
        handle = backend.launch(backend.transfer(prepared), keep_caches=True)
        out, stats = handle[1], handle[2]
        labels = backend.collect(handle)

    with spans.span("perfbench:reference"):
        rng = np.random.default_rng([state["cell"]["seed"], 64])
        sample = np.sort(rng.choice(
            len(first), size=min(model["reference_sample"], len(first)),
            replace=False))
        judged = reference.judge(
            backend.params, config, {key: model[key] for key in SAMPLER},
            np.asarray(prompt_ids)[sample], np.asarray(prompt_lens)[sample],
            reference.system_rows(out, stats, sample),
            tolerance=(reference.TEST_TOLERANCE if state["cell"]["rehearsal"]
                       else reference.TOLERANCE))
    del handle, out, stats  # the kept caches with them
    with spans.span("perfbench:warmup_job"):
        state["first_counts"] = batch_job._run_sentiment(
            state, os.path.join(state["out_dir"], "warmup", "sentiment"))["counts"]
    judged["width"] = int(np.asarray(prompt_ids).shape[1])
    judged["labels"] = sorted(set(labels))
    state["checks"]["reference"] = judged
