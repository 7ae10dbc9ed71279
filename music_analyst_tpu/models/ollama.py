"""Ollama HTTP passthrough backend — exact parity with the reference's live path.

The TPU-native backends (``mock``/``distilbert``/``llama``) replace the
per-song HTTP loop, but the original remote path remains available behind
the same flag surface (``--model ollama:<tag>``) for users migrating from
the reference: same endpoint contract (``$OLLAMA_ENDPOINT/api/generate``,
default ``http://localhost:11434``), same prompt template, same 4,000-char
truncation, same 120 s timeout, same first-token label normalization
(``scripts/sentiment_classifier.py:32-36,85-108``) — with the empty-response
``IndexError`` fixed (SURVEY.md §5 contract #5).
"""

from __future__ import annotations

import os
import time
from typing import List, Sequence

from music_analyst_tpu.models.backend import ClassifierBackend
from music_analyst_tpu.models.llama import LYRICS_TRUNCATION, PROMPT_TEMPLATE
from music_analyst_tpu.resilience.faults import fault_point
from music_analyst_tpu.resilience.policy import (
    RetryPolicy,
    classify_retryable,
    resolve_http_retries,
)
from music_analyst_tpu.telemetry import get_telemetry
from music_analyst_tpu.utils.labels import normalise_label

DEFAULT_ENDPOINT = "http://localhost:11434"


class OllamaClassifier(ClassifierBackend):
    name = "ollama"

    def __init__(
        self,
        model: str = "llama3",
        endpoint: str | None = None,
        timeout: float = 120.0,
        retries: int | None = None,
        backoff_seconds: float = 0.5,
    ) -> None:
        try:
            import requests  # noqa: F401
        except ImportError as exc:  # pragma: no cover - env-dependent
            raise RuntimeError(
                "The 'requests' package is required for the Ollama backend. "
                "Install it or use --mock."
            ) from exc
        self.model = model
        self.endpoint = endpoint or os.environ.get(
            "OLLAMA_ENDPOINT", DEFAULT_ENDPOINT
        )
        self.timeout = timeout
        # Transient-failure retries (upgrade over the reference, which
        # crashes the whole run on the first HTTP error, SURVEY.md §5
        # "Failure detection: fail-fast only").
        self.retries = resolve_http_retries(retries)
        self.backoff_seconds = backoff_seconds
        # Network-scale backoff: exponential from backoff_seconds with
        # full jitter, capped well below the request timeout, and never
        # sleeping past an armed bench deadline.
        self._retry = RetryPolicy(
            retries=self.retries,
            base_s=self.backoff_seconds,
            cap_s=min(30.0, max(self.backoff_seconds, timeout / 4.0)),
            classify=self._classify_exc,
        )
        self.last_latencies: List[float] = []

    @staticmethod
    def _classify_exc(exc: BaseException):
        """HTTP-aware retryability: 4xx (bar 408/429) is a verdict."""
        import requests

        if isinstance(exc, requests.RequestException):
            status = getattr(
                getattr(exc, "response", None), "status_code", None
            )
            if (status is not None and 400 <= status < 500
                    and status not in (408, 429)):
                return False, "http_client_error"
            return True, "http_error"
        return classify_retryable(exc)

    def _classify_one(self, lyrics: str) -> tuple[str, float]:
        import requests

        lyrics = lyrics.strip()
        if not lyrics:
            return "Neutral", 0.0  # reference classify() short-circuit
        payload = {
            "model": self.model,
            "prompt": PROMPT_TEMPLATE.format(lyrics=lyrics[:LYRICS_TRUNCATION]),
            "stream": False,
        }
        def _request() -> tuple[str, float]:
            fault_point("ollama.request", model=self.model)
            start = time.perf_counter()
            response = requests.post(
                f"{self.endpoint}/api/generate",
                json=payload,
                timeout=self.timeout,
            )
            elapsed = time.perf_counter() - start
            response.raise_for_status()
            raw_output = response.json().get("response", "").strip()
            get_telemetry().observe("ollama.request_seconds", elapsed)
            return normalise_label(raw_output), elapsed

        return self._retry.call(_request, site="ollama.request")

    def classify_batch(self, texts: Sequence[str]) -> List[str]:
        labels: List[str] = []
        self.last_latencies = []
        with get_telemetry().span("ollama_batch", rows=len(texts)):
            for text in texts:
                label, latency = self._classify_one(text)
                labels.append(label)
                self.last_latencies.append(latency)
        return labels
