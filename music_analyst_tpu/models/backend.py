"""The backend seam: what every engine and server knows about a model.

Three things live here, below ``engines/`` and ``serving/`` and above the
model files, so that neither of the two imports the other to build a
backend:

* :class:`ClassifierBackend` — the interface the model files implement
  (``classify_batch`` and the staged hooks the prefetch pipeline runs);
* :data:`FAMILIES` — the ONE table of model families: how a ``--model``
  name is matched, who loads it, and what the family takes (a mesh,
  ``length_buckets``, ``weight_quant``, a checkpoint variable).
  :func:`get_backend`, the CLI's usage checks, ``engines/validate.py`` and
  the router's replica set-up all read it; a family is recognised nowhere
  else;
* :class:`ModelResidency` — the owner of a backend's lifetime (load once,
  warm once, reload on a poisoned device), used by the batch sentiment
  engine, the weight validator, the batcher and the server alike.

Importing this module loads no model file and no JAX: a launcher (``serve
--replicas N``) asks the table whether a model runs on the device without
touching it.
"""

from __future__ import annotations

import dataclasses
import os
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from music_analyst_tpu.telemetry import get_telemetry


class ClassifierBackend:
    """Interface all sentiment backends implement."""

    name = "base"
    # Whether per-song latency is meaningful for this backend.  The
    # reference's mock path always records 0.0 (scripts/
    # sentiment_classifier.py:83) — mock sets this False to keep
    # sentiment_details.csv byte-identical; device model backends report
    # amortized batch latency instead of the reference's per-song HTTP time.
    reports_latency = True
    # Why no continuous decode runtime (``serving/decode_runtime.py``) can
    # host this backend, or ``None`` where one can: the one question
    # ``serve`` and the scheduler ask before the ``generate`` op exists.
    # A decoder answers for its own layers (``models/llama.py``).
    decode_runtime_refusal: Optional[str] = (
        "this backend runs no decoder a {runtime} runtime could host"
    )

    def classify_batch(self, texts: Sequence[str]) -> List[str]:
        """Labels for a batch of raw lyric strings."""
        raise NotImplementedError

    # Staged hooks for the host↔device prefetch pipeline
    # (music_analyst_tpu/runtime/prefetch.py).  The engine runs
    # ``prepare`` (host tokenize + batch planning), ``transfer``
    # (``jax.device_put`` of the wire payload), and ``launch`` (dispatch
    # the jitted forwards without blocking) in separate pipeline stages,
    # then blocks on ``collect`` in the consumer — so batch i+2 tokenizes
    # and batch i+1 transfers while batch i runs on the chips.  The
    # defaults collapse the three stages into ``submit``, so a backend
    # that only implements submit/collect (or just classify_batch) works
    # unchanged — the pipeline simply gets no tokenize/transfer overlap
    # from it.
    def prepare(self, texts: Sequence[str]):
        """Host-only work: tokenize + plan the batch.  Must not touch the
        device."""
        return texts

    def transfer(self, prepared):
        """Ship the prepared payload host→device (``jax.device_put``)."""
        return prepared

    def launch(self, transferred):
        """Dispatch device work for a transferred payload; returns the
        handle ``collect`` blocks on."""
        return self.submit(transferred)

    # Async pair kept as the single-call surface: ``submit`` does the host
    # work and dispatches device work without blocking; ``collect`` blocks
    # on the result.  Backends that implement the staged hooks above
    # compose them here so direct submit/collect callers see one behavior.
    def submit(self, texts: Sequence[str]):
        return self.classify_batch(texts)

    def collect(self, handle) -> List[str]:
        return handle


# ------------------------------------------------------------ the families

PRESET_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "presets")


def preset_files() -> Dict[str, str]:
    """``{preset name: path}`` of the decoder configurations kept as files
    (``models/presets/<name>.json``; ``models/llama.py`` builds them)."""
    return {
        entry[: -len(".json")]: os.path.join(PRESET_DIR, entry)
        for entry in sorted(os.listdir(PRESET_DIR))
        if entry.endswith(".json")
    }


def decoder_stems() -> Tuple[str, ...]:
    """What a decoder preset's name starts with: ``llama`` (the presets
    ``models/llama.py`` builds in code) and the leading letters of every
    preset file's name — so a new preset file is a recognised ``--model``
    name with no edit here or in an engine."""
    stems = ["llama"]
    for name in preset_files():
        stem = re.match(r"[a-z]*", name).group(0)
        if stem not in stems:
            stems.append(stem)
    return tuple(stems)


def _load_mock(model: str, **kwargs):
    from music_analyst_tpu.models.mock import MockKeywordClassifier

    return MockKeywordClassifier(**kwargs)


def _load_ollama(model: str, **kwargs):
    from music_analyst_tpu.models.ollama import OllamaClassifier

    tag = model.split(":", 1)[1] if ":" in model else "llama3"
    return OllamaClassifier(model=tag, **kwargs)


def _load_distilbert(model: str, **kwargs):
    from music_analyst_tpu.models.distilbert import DistilBertClassifier

    return DistilBertClassifier.from_pretrained_or_random(model, **kwargs)


def _load_decoder(model: str, **kwargs):
    from music_analyst_tpu.models.llama import LlamaZeroShotClassifier

    return LlamaZeroShotClassifier.from_pretrained_or_random(model, **kwargs)


@dataclasses.dataclass(frozen=True)
class Family:
    """One row of :data:`FAMILIES`."""

    name: str
    # How the family's ``--model`` names are written, for messages.
    spelling: Callable[[], Sequence[str]]
    matches: Callable[[str], bool]
    load: Callable[..., ClassifierBackend]
    # What the family takes.  ``mesh`` also says the model runs on the
    # device (a replica of it is pinned to a chip).
    mesh: bool = False
    length_buckets: bool = False
    weight_quant: bool = False
    # The variable a production run reads its checkpoint from; ``None`` =
    # no checkpoint to validate.
    checkpoint_env: Optional[str] = None


FAMILIES: Tuple[Family, ...] = (
    Family("mock", lambda: ("'mock'",), lambda m: m == "mock", _load_mock),
    Family("ollama", lambda: (),
           lambda m: m == "ollama" or m.startswith("ollama:"), _load_ollama),
    Family("distilbert", lambda: ("'distilbert*'",),
           lambda m: m.startswith("distilbert"), _load_distilbert,
           mesh=True, length_buckets=True, weight_quant=True,
           checkpoint_env="MUSICAAL_DISTILBERT_CKPT"),
    # A decoder refuses what its own layers cannot take (``weight_quant``
    # with experts, a checkpoint for kinds no loader maps) by name, in
    # ``models/llama.py``.
    Family("decoder", lambda: [f"'{s}*'" for s in decoder_stems()],
           lambda m: m.startswith(decoder_stems()), _load_decoder,
           mesh=True, weight_quant=True,
           checkpoint_env="MUSICAAL_LLAMA_CKPT"),
)


def family_of(model: str, mock: bool = False) -> Family:
    """The row a ``--model``/``--mock`` pair resolves to (``--mock`` wins,
    ``scripts/sentiment_classifier.py:140``)."""
    if mock:
        return FAMILIES[0]
    for family in FAMILIES:
        if family.matches(model):
            return family
    spelled = [s for family in FAMILIES for s in family.spelling()]
    raise ValueError(
        f"unknown model {model!r}: expected "
        + ", ".join(spelled[:-1]) + f" or {spelled[-1]}"
    )


def family_takes(model: str, mock: bool, option: str) -> bool:
    """Whether the resolved family takes ``option`` (``"mesh"``,
    ``"length_buckets"``, ``"weight_quant"``); an unknown name takes
    nothing — :func:`get_backend` is where it is refused.  For callers
    deciding whether to *build* a mesh at all (that initialises the device
    backend) or to turn a flag away as a usage error."""
    try:
        return bool(getattr(family_of(model, mock), option))
    except ValueError:
        return False


def has_buckets(length_buckets) -> bool:
    """Whether a ``length_buckets`` value actually requests bucketing.

    ``None`` and an empty sequence both mean "unset"; `len(...)` (not
    truthiness) so numpy arrays work as sequences; strings ("auto" or a
    mistaken "32,64") count as set and defer to the classifier's own
    validation for a clear message.  Shared by ``get_backend`` and
    ``run_sentiment``'s injected-backend guard so the two entry points
    agree on what "unset" means (r4 advisor finding).
    """
    if length_buckets is None:
        return False
    if isinstance(length_buckets, str):
        return True
    try:
        return len(length_buckets) > 0
    except TypeError:
        # A scalar (length_buckets=32) is a plausible slip for a
        # one-bucket list; name the misuse instead of letting a bare
        # `len(int)` TypeError surface from deep inside either caller.
        raise TypeError(
            "length_buckets must be a string ('auto') or a sequence of "
            f"ints, got {type(length_buckets).__name__}"
        ) from None


def get_backend(
    model: str,
    mock: bool = False,
    mesh=None,
    length_buckets: Optional[Sequence[int]] = None,
    weight_quant: Optional[str] = None,
    **kwargs,
) -> ClassifierBackend:
    """Resolve the ``--model``/``--mock`` flag surface to a backend.

    Mirrors the reference's dispatch (``--mock`` wins over ``--model``,
    ``scripts/sentiment_classifier.py:140``); model names map to on-device
    families instead of Ollama model tags.

    The table owns per-family capabilities, so callers pass
    ``mesh``/``length_buckets`` unconditionally: ``mesh`` shards model
    batches over dp and places params per the TP rules but is dropped for
    the mesh-incapable families (the keyword kernel, the Ollama HTTP
    passthrough); ``length_buckets`` is encoder-only and *raises* elsewhere
    (silently running every row at full length would defeat the flag).
    """
    buckets = has_buckets(length_buckets)
    has_wq = weight_quant not in (None, "none")
    try:
        family, unknown = family_of(model, mock), None
    except ValueError as exc:  # an option it cannot take is named first
        family, unknown = None, exc
    if buckets and not (family and family.length_buckets):
        raise ValueError(
            "length_buckets is an encoder-classifier option; "
            f"model {model!r} does not support it"
        )
    if has_wq and not (family and family.weight_quant):
        # Same posture as length_buckets: silently running float would
        # defeat the flag.
        raise ValueError(
            "weight_quant is an on-device model option; "
            f"model {model!r} does not support it"
        )
    if family is None:
        raise unknown
    if family.mesh and mesh is not None:
        kwargs["mesh"] = mesh
    if has_wq:
        kwargs["weight_quant"] = weight_quant
    if buckets:
        # Strings pass through (the classifier validates "auto" vs
        # mistakes); a sequence is normalized to a tuple.
        kwargs["length_buckets"] = (
            length_buckets if isinstance(length_buckets, str)
            else tuple(int(b) for b in length_buckets)
        )
    try:
        return family.load(model, **kwargs)
    except ImportError as exc:
        if not family.mesh:
            raise
        raise RuntimeError(
            f"model backend {model!r} is unavailable ({exc}); "
            "use --mock or --model mock for the keyword kernel"
        ) from exc


# --------------------------------------------------------------- residency

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
    """Load-once, warm-once holder for a classifier backend.

    The batch engines pay model load + XLA compile on every invocation and
    amortize it over a whole dataset; a server amortizes it over its
    *lifetime* instead.  This object owns that lifetime, everywhere:

    * **load once** — through :func:`get_backend`, so ``--weight-quant``
      streams the checkpoint through ``engines/checkpoint.
      load_quantized_params`` + the persistent ``wq_cache`` exactly like a
      batch run;
    * **pin** — the classifier (and its on-device params) is held by this
      object; nothing about the request path can drop it;
    * **warm explicitly** — :meth:`warmup` runs one dummy batch at every
      power-of-two bucket size the batcher can emit, so by the time the
      socket opens every steady-state shape is compiled (``--warmup``);
    * **reload** — the recovery half of reload-on-poisoned-device is one
      code path (:meth:`reload`) whichever surface hit the failure.

    Per-backend compile/warmup state is tracked in :meth:`snapshot` and
    lands in the run manifest's ``serving.residency`` section.
    """

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
