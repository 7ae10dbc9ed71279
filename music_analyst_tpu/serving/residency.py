"""Warm model residency: load once, compile once, answer forever.

The batch engines pay model load + XLA compile on every invocation and
amortize it over a whole dataset; a server amortizes it over its
*lifetime* instead.  This manager owns that lifetime:

* **load once** — the backend resolves through the same
  ``engines/sentiment.get_backend`` dispatch the CLI uses, so
  ``--weight-quant`` streams the checkpoint through
  ``engines/checkpoint.load_quantized_params`` + the persistent
  ``wq_cache`` exactly like a batch run;
* **pin for the server lifetime** — the classifier (and its on-device
  params) is held by this object until :meth:`release`; nothing about
  the request path can drop it;
* **warm explicitly** — :meth:`warmup` runs one dummy batch at every
  power-of-two bucket size the batcher can emit, so by the time the
  socket opens every steady-state shape is compiled and the first real
  request pays dispatch cost only (``--warmup``, default on).

Per-backend compile/warmup state is tracked in :meth:`snapshot` and
lands in the run manifest's ``serving.residency`` section.

This object is the single owner of a resident backend *everywhere*, not
just under the server: the batch sentiment engine and the weight
validator acquire through it too, so backend construction (mesh
placement, weight-quant streaming, length buckets)
is written once and reload-on-poisoned-device is one code path
(:meth:`reload`) whichever surface hit the failure.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from music_analyst_tpu.telemetry import get_telemetry


def warmup_sizes(max_batch: int) -> List[int]:
    """The power-of-two bucket ladder the batcher pads into: 1, 2, 4, …
    up to (and including) the bucket covering ``max_batch``."""
    sizes: List[int] = []
    size = 1
    while size < max_batch:
        sizes.append(size)
        size <<= 1
    sizes.append(size)
    return sizes


class ModelResidency:
    """Load-once, warm-once holder for a classifier backend."""

    def __init__(
        self,
        model: str = "mock",
        mock: bool = False,
        weight_quant: Optional[str] = None,
        mesh=None,
        backend=None,
        **backend_kwargs: Any,
    ) -> None:
        self.model = model
        self.mock = mock
        self.weight_quant = weight_quant
        self.mesh = mesh
        # Extra get_backend() options (length_buckets, checkpoint_path, …)
        # pinned at construction so a reload rebuilds the same backend.
        self.backend_kwargs = backend_kwargs
        self._backend = backend  # injected (tests) — skips loading
        self._lock = threading.Lock()
        self._state: Dict[str, Any] = {
            "model": model,
            "mock": bool(mock),
            "weight_quant": weight_quant or "none",
            "loaded": backend is not None,
            "load_seconds": 0.0,
            "warm": False,
            "warmup": None,
            "reloads": 0,
        }

    # ------------------------------------------------------------- loading

    def acquire(self):
        """The resident backend, loading it on first call (thread-safe)."""
        with self._lock:
            if self._backend is not None:
                return self._backend
            tel = get_telemetry()
            from music_analyst_tpu.engines.sentiment import get_backend

            t0 = time.perf_counter()
            with tel.span("serve.load", model=self.model,
                          weight_quant=self.weight_quant or "none"):
                self._backend = get_backend(
                    self.model,
                    mock=self.mock,
                    mesh=self.mesh,
                    weight_quant=self.weight_quant,
                    **self.backend_kwargs,
                )
            load_s = time.perf_counter() - t0
            self._state.update(
                loaded=True,
                backend=getattr(self._backend, "name", "injected"),
                load_seconds=round(load_s, 6),
            )
            # Streaming weight-quant loads leave per-unit staging stats;
            # surface them next to the residency record when present.
            try:
                from music_analyst_tpu.engines.checkpoint import (
                    last_load_stats,
                )

                load_stats = last_load_stats()
                if load_stats:
                    self._state["wq_load"] = load_stats
            except Exception:
                pass
            return self._backend

    # ------------------------------------------------------------- warmup

    def warmup(self, max_batch: int) -> Dict[str, Any]:
        """Compile every batcher bucket shape before the first request.

        Dummy rows are empty strings (empty lyric → Neutral is a golden
        contract, so this is semantically inert for every backend).
        Returns and records {sizes, seconds, compiles} where ``compiles``
        is the XLA compile count the warmup itself triggered.
        """
        clf = self.acquire()
        tel = get_telemetry()
        sizes = warmup_sizes(max_batch)
        before = tel.compile_stats()
        t0 = time.perf_counter()
        with tel.span("serve.warmup", sizes=sizes):
            for size in sizes:
                clf.collect(clf.submit([""] * size))
        warm_s = time.perf_counter() - t0
        after = tel.compile_stats()
        record = {
            "sizes": sizes,
            "seconds": round(warm_s, 6),
            "compiles": after["count"] - before["count"],
            "compile_seconds": round(
                after["seconds"] - before["seconds"], 6
            ),
        }
        with self._lock:
            self._state["warm"] = True
            self._state["warmup"] = record
        tel.annotate(serve_warmup=record)
        return record

    def warmup_decode(self, scheduler) -> Dict[str, Any]:
        """Compile the continuous-decode programs before the first
        ``generate`` request lands (the decode analogue of :meth:`warmup`:
        dummy prefill + decode dispatch + free — after this the runtime's
        zero-retrace contract holds for the server lifetime).  The paged
        runtime walks a ladder of shifted page-table rows so page-gather
        indices are exercised as traced operands, not baked constants:
        the same four programs must serve every later table permutation."""
        tel = get_telemetry()
        with tel.span("serve.warmup_decode"):
            record = scheduler.warmup()
        with self._lock:
            self._state["decode_warmup"] = record
        return record

    def release(self) -> None:
        with self._lock:
            self._backend = None
            self._state["loaded"] = False

    def current(self):
        """The resident backend (loading lazily) — resolve PER CALL so a
        :meth:`reload` swaps the backend under live ops."""
        backend = self._backend
        return backend if backend is not None else self.acquire()

    def reload(self):
        """Drop the (poisoned) backend and load a fresh one.

        The recovery half of reload-on-poisoned-device: the batcher's
        failover hook calls this when a dispatch failure classifies as
        device loss, then retries the batch against the new backend —
        the server survives the device dying between batches.
        """
        tel = get_telemetry()
        with self._lock:
            self._backend = None
            self._state["loaded"] = False
            self._state["warm"] = False
            self._state["reloads"] += 1
        tel.count("serving.residency_reloads")
        tel.event("residency_reload", model=self.model)
        return self.acquire()

    # ------------------------------------------------------------ readouts

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._state)
