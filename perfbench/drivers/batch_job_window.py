"""Driver ``batch_job_window``: ``batch_job``'s back-to-back jobs with the
zero-shot decoder whose attention layers differ in kind (full and
sliding-window grouped-query layers) in place of the encoder.

``run`` and the job loop are ``batch_job``'s own (imported, not copied), and
so is ``setup`` but for the model's part: the backend is built through
``get_backend``, its widths are checked against the configuration file key
by key (each attention kind's heads, window and RoPE, the experts held and
the vocabulary's slice among them), the corpus's first batch goes through
the timed path (``prepare`` / ``transfer`` / ``launch`` / ``collect``), a
seeded sample of its rows, most of them longer than the window, is compared
with ``reference/laguna_f32.py`` at the published widths, the timed shapes
and the same share (the experts the step chose, ties apart; the three label
scores; every layer's keys and values on the prompt's positions; labels
where the reference's margin exceeds the tolerance), and one whole job runs
outside the window.
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
    "head_dim": "head_dim", "intermediate_size": "hidden_dim",
    "moe_intermediate_size": "moe_hidden_dim", "vocab_size": "vocab_size",
    "num_experts": "experts_held_count",
    "num_experts_per_tok": "moe_top_k",
    "moe_routed_scaling_factor": "routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob",
    "tie_word_embeddings": "tie_embeddings",
    "rms_norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_seq_len",
}


def setup(cell: Dict[str, Any]) -> Dict[str, Any]:
    # batch_job.setup, its `_setup_sentiment` step being this file's
    encoder_setup = batch_job._setup_sentiment
    batch_job._setup_sentiment = _setup_window
    try:
        return batch_job.setup(cell)
    finally:
        batch_job._setup_sentiment = encoder_setup


def _kinds_stated(config) -> Dict[str, Any]:
    """What the configuration file says of each attention kind its layers
    use, in the layout of ``LlamaConfig.attention_kinds``."""
    layers = config["num_hidden_layers"]
    heads = dict(zip(config["layer_types"],
                     config["num_attention_heads_per_layer"][:layers]))
    out = {}
    for kind in sorted(set(config["layer_types"])):
        group = config["rope_parameters"][kind]
        rotary = int(config["head_dim"] * group.get(
            "partial_rotary_factor", 1))
        yarn = None
        if group["rope_type"] == "yarn":
            yarn = tuple(sorted(
                (k, v) for k, v in group.items()
                if k not in ("rope_type", "rope_theta",
                             "partial_rotary_factor")))
        out[kind] = (
            heads[kind],
            config["sliding_window"] if kind == "sliding_attention" else 0,
            float(group["rope_theta"]),
            0 if rotary == config["head_dim"] else rotary, yarn)
    return out


def _check_widths(backend, config) -> None:
    model, cfg = config["model"], backend.config
    for key, field in _WIDTHS.items():
        if getattr(cfg, field) != config[key]:
            raise SystemExit(
                f"perfbench: the backend's {field} is {getattr(cfg, field)}, "
                f"the configuration file's {key} says {config[key]}")
    layers = config["num_hidden_layers"]
    dense = len(config["mlp_only_layers"])
    stated = {
        "the router's width": (cfg.n_experts,
                               config["published"]["num_experts"]),
        "experts_held": (list(cfg.experts_held or ()),
                         model["experts_held"]),
        "layer_types": (list(cfg.layer_types or ()), config["layer_types"]),
        "the attention kinds": (
            {name: (k.n_heads, k.window, k.rope_theta, k.rotary_dim, k.yarn)
             for name, k in cfg.attention_kinds or ()},
            _kinds_stated(config)),
        "the dense layers": (
            [cfg.routed_layer(i) for i in range(layers)],
            [kind == "sparse" for kind in config["mlp_layer_types"][:layers]]),
        "mlp_only_layers": (cfg.first_k_dense_replace, dense),
        "the shared expert": (
            cfg.n_shared_experts * cfg.moe_hidden_dim,
            config["shared_expert_intermediate_size"]),
        "attn_impl": (cfg.attn_impl, model["attn_impl"]),
        "max_prompt_len": (backend.max_prompt_len, model["max_prompt_len"]),
        "dtype": (cfg.dtype, model["dtype"]),
        "param_dtype": (cfg.param_dtype, model["param_dtype"]),
        "prompt_width_floor": (cfg.prompt_width_floor,
                               model.get("prompt_width_floor", 64)),
        # the four readings of function the source has no key for
        "moe_router": (cfg.moe_router, model["moe_router"]),
        "gqa_output_gate": (cfg.gqa_output_gate, model["gqa_output_gate"]),
        "qk_norm": (cfg.qk_norm, model["qk_norm"]),
        "shared_expert_gate": (cfg.shared_expert_gate,
                               model["shared_expert_gate"]),
    }
    for what, (has, says) in stated.items():
        if has != says:
            raise SystemExit(
                f"perfbench: {what} is {has} in the backend, {says} in the "
                "configuration file")


def _sample(seed: int, lens, model) -> np.ndarray:
    """``reference_sample`` rows of the first step, drawn from ``--seed``:
    ``reference_long_rows`` of them among the rows longer than
    ``reference_long_tokens`` (as many as there are, where fewer), the rest
    among the others."""
    rng = np.random.default_rng([seed, 64])
    lens = np.asarray(lens)
    size = min(model["reference_sample"], len(lens))
    long_rows = np.flatnonzero(lens > model["reference_long_tokens"])
    n_long = min(model["reference_long_rows"], len(long_rows), size)
    taken = rng.choice(long_rows, size=n_long, replace=False)
    others = np.setdiff1d(np.arange(len(lens)), taken)
    rest = rng.choice(others, size=size - n_long, replace=False)
    return np.sort(np.concatenate([taken, rest])).astype(np.int64)


def judge(tol, texts, labels, scores, kept, judged) -> Dict[str, Any]:
    """The comparison that decides ``correct``, the one expression for this
    cell's set-up and for the controls of ``tools/window_reference_probe``:
    what a step answered on the sampled rows (their ``texts``, the
    ``labels`` it gave, its label ``scores``, and ``kept`` =
    ``reference.compare_kept`` of its caches) against a reference's reading
    of the same rows (``judged``: ``reference.label_scores``' result, the
    step's own choices handed over), under the limits ``tol``."""
    from music_analyst_tpu.utils.labels import SUPPORTED_LABELS
    from reference.laguna_f32 import KEPT_LIMITS

    want, routing = judged["scores"], judged["routing"]
    diff = np.abs(scores - want)
    compared, wrong = 0, []
    for i, (text, label, row) in enumerate(zip(texts, labels, want)):
        ranked = np.sort(row)
        if (ranked[-1] - ranked[-2] <= tol["label_margin"]
                or not text.strip()):
            continue
        compared += 1
        if label != SUPPORTED_LABELS[int(np.argmax(row))]:
            wrong.append(i)
    within = {
        "label_score_max": bool(diff.max() <= tol["label_score_max"]),
        "label_score_median": bool(
            np.median(diff) <= tol["label_score_median"]),
        "wrong_choices": routing["wrong"] <= tol["wrong_choices"],
        **{name: bool(kept[name] <= tol[name]) for name in KEPT_LIMITS},
        "labels": not wrong,
    }
    return {
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
        "limits_failed": sorted(k for k, held in within.items() if not held),
        "ok": all(within.values()),
    }


def first_step(backend, texts, sample_of):
    """``texts`` through the timed path (``prepare`` / ``transfer`` /
    ``launch`` / ``collect``) with the caches of the rows ``sample_of(
    prompt_lens)`` draws riding back with the scores (values of an argument
    of the timed program, not another program): the sample, and the step
    as :func:`against_reference` takes it."""
    default_probe = backend.probe_rows
    prepared = backend.prepare(texts)
    _, prompt_ids, prompt_lens = prepared
    sample = sample_of(prompt_lens)
    backend.probe_rows = np.resize(sample, default_probe.shape).astype(
        np.int32)
    try:
        handle = backend.launch(backend.transfer(prepared))
        scores, stats = np.asarray(handle[1], np.float64), handle[2]
        labels = backend.collect(handle)
    finally:
        backend.probe_rows = default_probe
    return sample, (texts, np.asarray(prompt_ids), np.asarray(prompt_lens),
                    scores, stats, labels)


def against_reference(backend, config, tol, step, sample,
                      variant="f32") -> Dict[str, Any]:
    """The sampled rows of ``step`` judged by ``reference/laguna_f32.py``
    (``variant``: its precision) at the configuration's widths and share:
    the experts the step ran for these rows handed over (the reference
    takes them where they are ties and counts the rest as wrong), then
    :func:`judge`."""
    from reference import laguna_f32 as reference

    texts, prompt_ids, prompt_lens, scores, stats, labels = step
    lens = prompt_lens[sample]
    prefer = reference.prefer_from_system(
        np.asarray(stats["chosen"])[:, sample],
        np.asarray(stats["chosen_labels"])[:, :, sample], lens)
    judged = reference.label_scores(
        backend.params, config, prompt_ids[sample], lens,
        backend._label_ids, backend._label_lens, variant=variant,
        prefer=prefer, margin=tol["route_margin"],
        rows_block=config["model"].get("reference_rows_block", 4))
    probe = {name: np.asarray(value)[:, :len(sample)]
             for name, value in stats["probe"].items()}
    kept = reference.compare_kept(judged["kept"], probe, lens)
    return {
        "rows": int(len(sample)), "width": int(prompt_ids.shape[1]),
        "row_tokens": [int(n) for n in lens],
        **judge(tol, [texts[i] for i in sample], [labels[i] for i in sample],
                scores[sample], kept, judged),
    }


def _setup_window(state, mesh_shape) -> None:
    from music_analyst_tpu.engines.sentiment import get_backend
    from reference import laguna_f32 as reference

    config, spans = state["config"], state["spans"]
    model = config["model"]
    if mesh_shape:
        raise SystemExit("perfbench: batch_job_window runs one chip")
    t0 = time.monotonic()
    backend = get_backend(model["name"])
    state["setup"]["backend_init_s"] = time.monotonic() - t0
    spans.add("perfbench:backend_init", t0, state["setup"]["backend_init_s"])
    _check_widths(backend, config)
    state["backend"] = backend

    first = [row[3] for row in corpus.read_rows(
        state["csv_path"], limit=state["batch_size"])]
    with spans.span("perfbench:first_batch"):
        sample, step = first_step(
            backend, first,
            lambda lens: _sample(state["cell"]["seed"], lens, model))
    # a rehearsal runs the test size, whose limits are the test size's
    tol = (reference.TEST_TOLERANCE if state["cell"]["rehearsal"]
           else reference.TOLERANCE)
    with spans.span("perfbench:reference"):
        state["checks"]["reference"] = against_reference(
            backend, config, tol, step, sample)
    with spans.span("perfbench:warmup_job"):
        state["first_counts"] = batch_job._run_sentiment(
            state, os.path.join(state["out_dir"], "warmup", "sentiment"))["counts"]
