"""The continuous decode runtimes, built from what a runtime really needs.

A *decoder* here is anything holding ``model``, ``params``, ``config``,
``tokenizer``, ``mesh`` and ``max_prompt_len`` — ``models/llama.py``'s
zero-shot classifier is the canonical one, but nothing below reads its
sentiment side.  The scheduler (``serving/decode_loop.py``) is the only
caller: it asks :func:`decode_runtime_refusal` whether a runtime can host
the backend at all, then builds one of the two cache layouts.
"""

from __future__ import annotations

import math
from typing import Optional

from music_analyst_tpu.models.backend import ClassifierBackend
from music_analyst_tpu.models.tokenization import ByteTokenizer
from music_analyst_tpu.ops.kv_pages import PagedDecodeRuntime, PagePlan
from music_analyst_tpu.ops.kv_slots import SlotDecodeRuntime, SlotPlan
from music_analyst_tpu.utils.shapes import round_pow2


def decode_runtime_refusal(backend, runtime: str) -> Optional[str]:
    """Why no ``runtime`` ("slot", "paged", "continuous decode") can host
    ``backend``, or ``None`` where one can — the backend's own answer
    (``ClassifierBackend.decode_runtime_refusal``; a model answers for its
    layers: a latent cache no page layout holds yet, a step that yields a
    block and not a token, or a recurrent state a row beside the cache,
    which slots and pages of keys and values have no place for)."""
    reason = getattr(backend, "decode_runtime_refusal",
                     ClassifierBackend.decode_runtime_refusal)
    return reason.format(runtime=runtime) if reason else None


def _prompt_geometry(decoder, prefill_chunk: int,
                     prompt_region: Optional[int]):
    """``(chunk, region)``: the prefill chunk clamped to the decoder's
    prompt cap, and the prompt region rounded up to whole chunks."""
    chunk = max(1, min(int(prefill_chunk), decoder.max_prompt_len))
    if prompt_region is None:
        prompt_region = decoder.max_prompt_len
    region = min(int(prompt_region), decoder.max_prompt_len)
    return chunk, max(chunk, chunk * ((region + chunk - 1) // chunk))


def _eos_id(decoder) -> int:
    return getattr(decoder.tokenizer, "eos_id", ByteTokenizer.EOS)


def slot_runtime(
    decoder,
    n_slots: int = 8,
    prefill_chunk: int = 64,
    max_new_tokens: int = 16,
    prompt_region: Optional[int] = None,
    decode_span: int = 4,
) -> SlotDecodeRuntime:
    """The continuous-batching device runtime over the monolithic slot
    cache (``ops/kv_slots.py``) for ``decoder``."""
    refusal = decode_runtime_refusal(decoder, "slot")
    if refusal:
        raise NotImplementedError(refusal)
    chunk, region = _prompt_geometry(decoder, prefill_chunk, prompt_region)
    plan = SlotPlan(
        n_slots=int(n_slots),
        prefill_chunk=chunk,
        prompt_region=region,
        max_new=int(max_new_tokens),
        decode_span=int(decode_span),
    )
    return SlotDecodeRuntime(decoder.model, decoder.config, plan,
                             _eos_id(decoder), mesh=decoder.mesh)


def paged_runtime(
    decoder,
    n_slots: int = 8,
    prefill_chunk: int = 64,
    max_new_tokens: int = 16,
    prompt_region: Optional[int] = None,
    decode_span: int = 4,
    page_size: int = 16,
    kv_pages: int = 0,
    kv_quant: str = "none",
) -> PagedDecodeRuntime:
    """The prefix-shared paged decode runtime for ``decoder``.

    The paged sibling of :func:`slot_runtime` (and the default KV
    backend): the per-slot KV buffer becomes a view through an int32 page
    table over a shared page pool, so sequences with a common token
    prefix — every zero-shot prompt shares ``PROMPT_TEMPLATE``'s head —
    can map the same physical pages.  Prefix identity is keyed on
    *token ids* (whatever tokenizer is resolved), not on text, so
    byte/llama tokenizers share exactly what their encodings share.
    ``kv_pages=0`` auto-sizes the pool to one full sequence per slot.
    ``kv_quant="int8"`` stores the page pool as int8 codes with
    per-(page, row) scales, dequantized inside the fused
    paged-attention kernel (ops/paged_attention.py).
    """
    refusal = decode_runtime_refusal(decoder, "paged")
    if refusal:
        raise NotImplementedError(refusal)
    chunk, region = _prompt_geometry(decoder, prefill_chunk, prompt_region)
    page = min(round_pow2(max(1, int(page_size)), 1), region)
    # The region must be a multiple of both the chunk and the page.
    unit = math.lcm(chunk, page)
    region = unit * ((region + unit - 1) // unit)
    pages_per_slot = region // page + -(-int(max_new_tokens) // page)
    n_pages = int(kv_pages) or int(n_slots) * pages_per_slot
    n_pages = max(n_pages, int(n_slots), pages_per_slot)
    plan = PagePlan(
        n_slots=int(n_slots),
        prefill_chunk=chunk,
        prompt_region=region,
        max_new=int(max_new_tokens),
        decode_span=int(decode_span),
        page_size=page,
        n_pages=n_pages,
    )
    return PagedDecodeRuntime(decoder.model, decoder.config, plan,
                              _eos_id(decoder), mesh=decoder.mesh,
                              kv_quant=kv_quant)
