"""Driver ``batch_job_mamba1``: ``batch_job``'s back-to-back jobs with the
Jamba (Mamba-1 + grouped-query attention) zero-shot decoder's set-up in
place of the encoder's.

``run`` and the job loop are ``batch_job``'s own (imported, not copied), and
so is ``setup`` but for the model's part: the backend is built through
``get_backend``, its widths are checked against the configuration file key
by key, the corpus's first batch goes through the timed path (``prepare`` /
``transfer`` / ``launch`` / ``collect``), a seeded sample of its rows is
compared with ``reference/jamba_f32.py`` at the published widths, the timed
shapes and the same share (the three label scores; the logits at the
prompt's last position; every Mamba-1 layer's state and convolution tail
after the prompt and the attention layers' keys and values; the first
Mamba-1 layer's state against the float32 recurrence on that layer's own
inputs as the program read them; labels where the reference's margin
exceeds the tolerance), and one whole job runs outside the window.
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
    "intermediate_size": "hidden_dim", "vocab_size": "vocab_size",
    "mamba_d_state": "mamba_d_state", "mamba_dt_rank": "mamba_dt_rank",
    "mamba_d_conv": "mamba_conv_kernel",
    "tie_word_embeddings": "tie_embeddings",
    "rms_norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_seq_len",
}


def setup(cell: Dict[str, Any]) -> Dict[str, Any]:
    # batch_job.setup, its `_setup_sentiment` step being this file's
    encoder_setup = batch_job._setup_sentiment
    batch_job._setup_sentiment = _setup_mamba1
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
        "the inner width": (cfg.mamba_inner_dim,
                            config["mamba_expand"] * config["hidden_size"]),
        "layer_types": (list(cfg.layer_types or ()), model["layer_types"]),
        "routed experts": (cfg.n_experts, 0),
        "positions": (cfg.use_rope, False),
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


def _setup_mamba1(state, mesh_shape) -> None:
    from music_analyst_tpu.engines.sentiment import get_backend
    from music_analyst_tpu.utils.labels import SUPPORTED_LABELS
    from reference import jamba_f32 as reference

    config, spans = state["config"], state["spans"]
    model = config["model"]
    if mesh_shape:
        raise SystemExit("perfbench: batch_job_mamba1 runs one chip")
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
        judged = reference.label_scores(
            backend.params, config, np.asarray(prompt_ids)[sample], lens,
            backend._label_ids, backend._label_lens,
            rows_block=int(model.get("reference_rows_block", 1)))
        # the logits and the scan's inputs are a row's; every other part
        # is stacked by layer
        probe = {name: (np.asarray(value)[:len(sample)]
                        if name == "logits" or name.startswith("scan_")
                        else np.asarray(value)[:, :len(sample)])
                 for name, value in stats["probe"].items()}
        readings = {
            **reference.compare(judged, scores[sample], probe, lens),
            **reference.scan_readings(backend.params, config, probe, lens)}
    want = judged["scores"]
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
        state["first_counts"] = batch_job._run_sentiment(state, os.path.join(
            state["out_dir"], "warmup", "sentiment"))["counts"]
    within = reference.within(readings, tol)
    state["checks"]["reference"] = {
        "rows": int(len(sample)),
        "width": int(np.asarray(prompt_ids).shape[1]),
        "tolerance": tol, **readings,
        "broken": sorted(name for name, ok in within.items() if not ok),
        "labels_compared": compared, "labels_wrong": wrong,
        "ok": bool(all(within.values()) and not wrong),
    }
