"""Real-weight validation harness (VERDICT r4 missing #2).

The reference's live path produces real labels from a real model
(``scripts/sentiment_classifier.py:85-108``); this framework's neural
backends run random weights in the zero-egress build environment, with
checkpoint loaders oracle-tested at the tensor level.  This module closes
the remaining certification gap: ONE command that, the moment real
weights are available via the ``MUSICAAL_*_CKPT`` env vars, runs a
dataset slice through the TPU backend AND through an independent
HuggingFace-``transformers`` torch oracle built from the same checkpoint
file, and reports label agreement.

    MUSICAAL_DISTILBERT_CKPT=…/pytorch_model.bin \\
        python -m music_analyst_tpu validate data.csv --model distilbert

The oracle is deliberately *not* this package's model code: logits come
from ``transformers``' own ``DistilBertForSequenceClassification`` /
``LlamaForCausalLM`` modules loaded with the checkpoint's state dict, so
a mapping or architecture bug on our side cannot cancel out.  Token ids
are shared (the backend's tokenizer feeds both), so the report isolates
model-path fidelity; tokenizer fidelity is covered by its own oracle
tests.  CI exercises the whole harness with crafted tiny checkpoints
(``tests/test_validate_weights.py``).
"""

from __future__ import annotations

import json
import os
from typing import List, Optional, Sequence

import numpy as np

from music_analyst_tpu.utils.labels import SUPPORTED_LABELS


def _oracle_distilbert_labels(
    checkpoint_path: str, clf, texts: Sequence[str]
) -> List[str]:
    """Labels from transformers' own DistilBERT given the same checkpoint,
    the same token ids, and the same documented 2→3-label rule."""
    import torch
    import transformers

    cfg = clf.config
    hf_cfg = transformers.DistilBertConfig(
        vocab_size=cfg.vocab_size,
        dim=cfg.dim,
        n_layers=cfg.n_layers,
        n_heads=cfg.n_heads,
        hidden_dim=cfg.hidden_dim,
        max_position_embeddings=cfg.max_positions,
        num_labels=cfg.n_classes,
        dropout=0.0,
        attention_dropout=0.0,
        seq_classif_dropout=0.0,
    )
    model = transformers.DistilBertForSequenceClassification(hf_cfg)
    sd = torch.load(checkpoint_path, map_location="cpu", weights_only=True)
    missing, unexpected = model.load_state_dict(sd, strict=False)
    unexpected = [k for k in unexpected if not k.endswith("position_ids")]
    if missing or unexpected:
        raise ValueError(
            "oracle could not consume the checkpoint exactly: "
            f"missing={sorted(missing)[:4]} unexpected={sorted(unexpected)[:4]}"
        )
    model.eval()

    ids, lengths = clf.tokenizer.encode_batch(texts, clf.max_len)
    attention = (
        np.arange(clf.max_len)[None, :] < lengths[:, None]
    ).astype(np.int64)
    with torch.no_grad():
        logits = model(
            input_ids=torch.tensor(np.asarray(ids, dtype=np.int64)),
            attention_mask=torch.tensor(attention),
        ).logits
    probs = torch.softmax(logits, dim=-1)
    conf, cls = probs.max(dim=-1)
    labels = []
    for text, c, k in zip(texts, conf.tolist(), cls.tolist()):
        if not text.strip():
            labels.append("Neutral")  # reference empty-lyric rule
        elif c < clf.neutral_threshold:
            labels.append("Neutral")
        else:
            labels.append(clf._CLASS_LABELS[int(k)])
    return labels


def build_llama_oracle(checkpoint_path: str, cfg):
    """transformers' own LlamaForCausalLM loaded from the checkpoint.

    Exposed separately from the label scoring so tests can pin logit
    parity directly (label agreement on random tiny fixtures is chaotic
    over ~250-token prompts — fp reduction-order noise can flip a near-tie
    even when both models are exact; real finetuned weights separate the
    labels by orders of magnitude more).
    """
    import transformers

    from music_analyst_tpu.models.llama import load_torch_state_dict

    # Same shard-merging reader as the backend: MUSICAAL_LLAMA_CKPT may be
    # a single file or a directory of pytorch_model-*.bin shards.
    sd = load_torch_state_dict(checkpoint_path)
    if not any(k.startswith("model.") for k in sd):
        # The backend tolerates bare-model keys; HF's module names don't.
        sd = {
            (k if k == "lm_head.weight" else "model." + k): v
            for k, v in sd.items()
        }
    hf_cfg = transformers.LlamaConfig(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.dim,
        intermediate_size=cfg.hidden_dim,
        num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads,
        max_position_embeddings=cfg.max_seq_len,
        rope_theta=cfg.rope_theta,
        rms_norm_eps=1e-5,  # models/layers.py RMSNorm epsilon
        attention_bias=False,
        tie_word_embeddings="lm_head.weight" not in sd,
    )
    model = transformers.LlamaForCausalLM(hf_cfg)
    missing, unexpected = model.load_state_dict(sd, strict=False)
    missing = [k for k in missing if k != "lm_head.weight"]  # tied
    unexpected = [k for k in unexpected if "rotary_emb" not in k]
    if missing or unexpected:
        raise ValueError(
            "oracle could not consume the checkpoint exactly: "
            f"missing={sorted(missing)[:4]} unexpected={sorted(unexpected)[:4]}"
        )
    model.eval()
    return model


def _oracle_llama_labels(
    checkpoint_path: str, clf, texts: Sequence[str]
) -> List[str]:
    """Labels from transformers' LlamaForCausalLM, scoring the same label
    continuations teacher-forced after the same prompt ids."""
    import torch

    from music_analyst_tpu.models.llama import (
        LYRICS_TRUNCATION,
        PROMPT_TEMPLATE,
    )

    model = build_llama_oracle(checkpoint_path, clf.config)

    label_ids = [
        [int(t) for t in clf._label_ids[k][: clf._label_lens[k]]]
        for k in range(len(SUPPORTED_LABELS))
    ]
    labels = []
    for text in texts:
        if not text.strip():
            labels.append("Neutral")  # reference empty-lyric rule
            continue
        prompt = PROMPT_TEMPLATE.format(lyrics=text.strip()[:LYRICS_TRUNCATION])
        row, n = clf.tokenizer.encode(prompt, clf.max_prompt_len)
        prompt_ids = [int(t) for t in row[:n]]
        # One batched forward scores all three right-padded continuations
        # (the rows differ only in their ≤8-token tails; per-label
        # forwards would recompute the ~250-token prompt three times).
        width = n + max(len(c) for c in label_ids)
        batch = torch.zeros((len(label_ids), width), dtype=torch.long)
        attention = torch.zeros_like(batch)
        for k, cont in enumerate(label_ids):
            seq = prompt_ids + cont
            batch[k, : len(seq)] = torch.tensor(seq)
            attention[k, : len(seq)] = 1
        with torch.no_grad():
            logits = model(batch, attention_mask=attention).logits
        logp = torch.log_softmax(logits.float(), dim=-1)
        scores = []
        for k, cont in enumerate(label_ids):
            # Token cont[j] is predicted by the position before it.
            total = sum(
                float(logp[k, n - 1 + j, tok])
                for j, tok in enumerate(cont)
            )
            # Length-normalized, like the backend's scorer: summed
            # log-probs would favor the shortest label
            # (models/llama.py:_score_labels).
            scores.append(total / max(1, len(cont)))
        labels.append(SUPPORTED_LABELS[int(np.argmax(scores))])
    return labels


def run_validation(
    dataset_path: str,
    model: str = "distilbert",
    limit: int = 64,
    output_dir: Optional[str] = None,
    checkpoint_path: Optional[str] = None,
    quiet: bool = False,
    backend=None,
    weight_quant: Optional[str] = None,
):
    """Classify a slice with the TPU backend and with the HF torch oracle;
    return the agreement report (and write ``weight_validation.json``).

    ``backend`` is injectable for tests; by default the model name
    resolves through :func:`get_backend`, which picks the checkpoint up
    from the same ``MUSICAAL_*_CKPT`` env var a production run uses.
    """
    from music_analyst_tpu.data.csv_io import iter_songs
    from music_analyst_tpu.models.backend import ModelResidency, family_of

    try:
        family = family_of(model)
    except ValueError:
        family = None
    if family is None or not family.checkpoint_env:
        raise ValueError(
            f"validate supports distilbert[-*] and llama[3*] models, got "
            f"{model!r} (mock/ollama have no checkpoint to validate)"
        )
    checkpoint_path = checkpoint_path or os.environ.get(
        family.checkpoint_env
    )
    if not checkpoint_path:
        raise RuntimeError(
            f"no checkpoint to validate: set {family.checkpoint_env} (or "
            "pass checkpoint_path=)"
        )
    clf = ModelResidency(
        model, backend=backend, weight_quant=weight_quant,
        checkpoint_path=checkpoint_path,
    ).acquire()
    if not getattr(clf, "pretrained", False):
        raise RuntimeError(
            "backend did not load the checkpoint — validating random "
            "weights would certify nothing"
        )

    from music_analyst_tpu.telemetry import get_telemetry

    tel = get_telemetry()
    with tel.run_scope("validate", output_dir):
        tel.annotate(model=model, backend=getattr(clf, "name", model))
        with tel.span("ingest"):
            songs = []
            for artist, song, text in iter_songs(dataset_path):
                songs.append((artist, song, text))
                if limit and len(songs) >= limit:
                    break
            texts = [text for _, _, text in songs]
        tel.count("rows_validated", len(texts))

        with tel.span("compute", rows=len(texts)):
            ours = clf.classify_batch(texts)
        with tel.span("oracle", rows=len(texts)):
            oracle = (
                _oracle_distilbert_labels(checkpoint_path, clf, texts)
                if family.name == "distilbert"
                else _oracle_llama_labels(checkpoint_path, clf, texts)
            )

        disagreements = [
            {"artist": a, "song": s, "ours": o, "oracle": h}
            for (a, s, _), o, h in zip(songs, ours, oracle)
            if o != h
        ]
        confusion = {
            want: {got: 0 for got in SUPPORTED_LABELS}
            for want in SUPPORTED_LABELS
        }
        for o, h in zip(ours, oracle):
            confusion[h][o] += 1
        report = {
            "model": model,
            "checkpoint": checkpoint_path,
            "rows": len(texts),
            # Unrounded: the CLI --min-agreement gate compares this value,
            # and rounding could nudge a just-failing run over the bar.
            "agreement": sum(
                o == h for o, h in zip(ours, oracle)
            ) / max(1, len(texts)),
            "oracle": "transformers torch forward, shared tokenizer ids",
            "confusion_oracle_to_ours": confusion,
            "disagreements": disagreements[:20],
        }
        if output_dir:
            os.makedirs(output_dir, exist_ok=True)
            path = os.path.join(output_dir, "weight_validation.json")
            with tel.span("write"), open(path, "w", encoding="utf-8") as fh:
                json.dump(report, fh, indent=2)
            if not quiet:
                print(f"Validation report -> {path}")
    if not quiet:
        print(
            f"{report['rows']} rows: {report['agreement'] * 100:.1f}% label "
            f"agreement vs the transformers oracle"
        )
        for d in disagreements[:5]:
            print(f"  differs: {d['song']!r} ours={d['ours']} "
                  f"oracle={d['oracle']}")
    return report
