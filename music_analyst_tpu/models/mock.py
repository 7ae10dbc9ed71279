"""The ``--mock`` classifier backend: keyword kernel on device.

Reference behavior being reproduced (``scripts/sentiment_classifier.py:
57-83``): strip the lyric; empty → Neutral; otherwise substring-score the
ten keywords and label by sign.  The scoring itself runs batched on device
(``ops/keyword_sentiment.py``); this wrapper owns batching policy and the
empty-lyric short-circuit.
"""

from __future__ import annotations

from typing import List, Sequence


from music_analyst_tpu.models.backend import ClassifierBackend
from music_analyst_tpu.ops.keyword_sentiment import score_texts
from music_analyst_tpu.utils.labels import score_to_label


class MockKeywordClassifier(ClassifierBackend):
    name = "mock"
    # Reference mock records latency 0.0 for every song
    # (scripts/sentiment_classifier.py:83).
    reports_latency = False

    def __init__(self, window_bytes: int = 4096) -> None:
        self.window_bytes = window_bytes

    def classify_batch(self, texts: Sequence[str]) -> List[str]:
        scores = score_texts(texts, length=self.window_bytes)
        # Empty (post-strip) lyrics score 0 → Neutral, identical to the
        # reference's explicit short-circuit (classify(), :60-61).
        return [score_to_label(int(s)) for s in scores]
