"""Flat vs length-bucketed encoder classify throughput.

Answers the round-3 open question: does sequence-length bucketing
(``models/distilbert.py:submit``) actually buy songs/s, and on what corpus?
Two corpora bracket the answer:

* ``long`` — the headline benchmark's own distribution (mean 180 words,
  ~84% of rows at the seq-128 cap): bucketing is expected to be a wash
  here, and ``derive_length_buckets`` should return no buckets at all.
* ``short`` — a short-lyric skew (mean 45 words, most rows ≤64 tokens):
  the distribution bucketing exists for; sub-quadratic attention + linear
  MLP FLOPs in seq should show up as a real win.

The auto path (``length_buckets="auto"``) is what's measured — the same
configuration ``bench.py`` and ``--length-buckets auto`` ship — so the
captured number is the shipped behavior, not a hand-tuned one.  A third
column measures sequence *packing* (``packed=True`` — several lyrics per
row behind a block-diagonal mask, ``models/distilbert.py:pack_segments``):
buckets and packing are the two exclusive right-sizing levers, and this
suite is the A/B that decides which one the engine should default to.
"""

from __future__ import annotations

import numpy as np

from benchmarks import suite
from benchmarks._util import device_info, smoke, timed


def _corpus(mean_words: int, n: int, seed: int) -> list:
    """Synthetic lyrics with the generator's word stock and length model."""
    from music_analyst_tpu.data.synthetic import _WORDS

    rng = np.random.default_rng(seed)
    words = np.array(_WORDS)
    texts = []
    for _ in range(n):
        n_words = max(3, int(rng.normal(mean_words, mean_words // 3)))
        texts.append(" ".join(rng.choice(words, size=n_words)))
    return texts


def _measure(texts, max_len: int, cfg, buckets, params=None,
             packed=False) -> dict:
    from music_analyst_tpu.models.distilbert import DistilBertClassifier

    clf = DistilBertClassifier(
        config=cfg, max_len=max_len, seed=0, length_buckets=buckets,
        packed=packed,
    )
    if params is not None:
        # Share one param tree across the flat/auto pair: the ~260 MB
        # host→device transfer happens once per corpus, and the
        # label-agreement number isolates bucketing.
        clf.params = params
    labels = clf.classify_batch(texts)  # compile + resolve auto buckets
    secs, _ = timed(lambda: clf.classify_batch(texts) or 0, repeats=2)
    return {
        "songs_per_s": round(len(texts) / secs, 1),
        "resolved_buckets": list(clf.length_buckets or ()),
        "labels": labels,
        "params": clf.params,
    }


@suite("bucketing")
def run() -> dict:
    from music_analyst_tpu.models.distilbert import DistilBertConfig

    if smoke():
        cfg, batch, max_len = DistilBertConfig.tiny(), 128, 64
    else:
        cfg, batch, max_len = DistilBertConfig(), 8192, 128

    out = {"suite": "bucketing", **device_info(), "smoke": smoke(),
           "batch": batch, "max_len": max_len}
    for name, mean_words in (("long", 180), ("short", 45)):
        texts = _corpus(mean_words, batch, seed=7)
        flat = _measure(texts, max_len, cfg, None)
        auto = _measure(texts, max_len, cfg, "auto", params=flat["params"])
        # Packed batching (SURVEY §7): same right-sizing goal as buckets,
        # opposite mechanism — fewer, fuller rows instead of narrower
        # ones.  Same params so the three labels columns are comparable.
        packed = _measure(
            texts, max_len, cfg, None, params=flat["params"], packed=True
        )
        agree = sum(
            a == b for a, b in zip(flat["labels"], auto["labels"])
        ) / batch
        agree_packed = sum(
            a == b for a, b in zip(flat["labels"], packed["labels"])
        ) / batch
        out[name] = {
            "mean_words": mean_words,
            "flat_songs_per_s": flat["songs_per_s"],
            "auto_songs_per_s": auto["songs_per_s"],
            "packed_songs_per_s": packed["songs_per_s"],
            "auto_buckets": auto["resolved_buckets"],
            "speedup": round(auto["songs_per_s"] / flat["songs_per_s"], 3),
            "speedup_packed": round(
                packed["songs_per_s"] / flat["songs_per_s"], 3
            ),
            "label_agreement": round(agree, 4),
            "label_agreement_packed": round(agree_packed, 4),
        }
    return out
