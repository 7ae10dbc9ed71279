"""Online serving layer: dynamic batching, admission control, residency.

The batch engines answer "analyze this corpus"; this package answers
"keep the model warm and answer requests as they arrive" — the
production-inference shape the ROADMAP north star asks for:

* :mod:`music_analyst_tpu.serving.batcher` — deadline-aware dynamic
  batcher (flush on ``max_batch`` or ``max_wait_ms``) with bounded
  admission queues that shed via structured ``queue_full`` errors;
* :class:`music_analyst_tpu.models.backend.ModelResidency` (below this
  package: the batch engines use it too) — load-once / warm-once
  backend holder (weight-quant + persistent caches included);
* :mod:`music_analyst_tpu.serving.server` — NDJSON protocol over a unix
  socket or stdio, graceful SIGTERM drain, watchdog + flight-recorder
  integration (the ``serve`` CLI subcommand);
* :mod:`music_analyst_tpu.serving.decode_loop` — continuous-batching
  decode scheduler (admit→prefill→decode over the slot-indexed KV cache
  in ``ops/kv_slots.py``) hosting the ``generate`` op;
* :mod:`music_analyst_tpu.serving.journal` — durable request journal
  (CRC-framed WAL): replay admitted-but-unanswered requests after a
  crash, dedup already-sent replies — exactly-once at the wire;
* :mod:`music_analyst_tpu.serving.decode_runtime` — the two decode
  runtimes built from a decoder's parts, and the one question asked of a
  backend before the ``generate`` op exists.

Import the module you need: the package itself imports none of them (a
router parent stays off the scheduler, a batch job off all of it).
"""
