"""Prefix-shared paged KV cache for the continuous decode runtime.

``ops/kv_slots.py`` gave each slot a monolithic KV region sized for
``max_total``, so the dominant generation workload — llama zero-shot
classification, which prepends the *same* prompt template to every song —
re-prefills and re-stores near-identical KV bytes for every request.
This module splits the cache into a fixed device-resident pool of
pow2-sized **pages**; a slot's KV buffer is now a *view* gathered through
an int32 page table, so two sequences with a common token prefix can map
the same physical pages and a prefix hit turns most of chunked prefill
into a page-table update plus a short suffix prefill.

Device half (this file, compiled): **five fixed-shape programs** via
:func:`profiled_jit` — the zero-retrace discipline of ``kv_slots`` with
the page table as a traced operand, so the programs never retrace as
pages are shared, copied, and recycled:

* **paged prefill chunk** — gather one slot's pages into a contiguous
  ``[1, max_total]`` view, run the *identical* chunk-prefill math as the
  monolithic runtime, scatter the touched pages back.  The view is
  byte-for-byte the monolithic slot buffer, so every attention reduction
  sees the same values at the same indices — continuous greedy tokens
  stay byte-identical to ``kv_slots`` and static ``generate_batch``.
* **paged decode step** — gather all slots' views through the full
  ``[n_slots, pages_per_slot]`` table, run the identical ``decode_span``
  scan, scatter back only each slot's *decode* pages (never below
  ``prompt_region``, so shared prompt pages are never written by decode).
* **paged verify block** — score a ``[n_slots, K]`` drafted token block
  (speculative decoding) in one dispatch over the gathered views,
  scattering back decode pages only — the paged twin of
  ``slots.verify``.
* **page free** — zero a mask of physical pages (failure-path hard
  isolation, the paged analogue of ``slots.free``).
* **page copy** — one page ``src → dst`` (copy-on-write for the
  partially-filled boundary page of a prefix hit).

Host half (pure Python, no jax): :class:`PagePool` (free list +
per-page refcounts: ``slot_refs`` = slots currently mapping the page,
``in_tree`` = the radix index holds it) and :class:`RadixIndex` (a radix
tree over page-granular token runs: match walks full-page children then
takes the longest-common-prefix partial; insert happens at
prefill-complete; a refcount-aware LRU evicts cold *leaves* only, never
a pinned page).  Both are deliberately jax-free so
``tests/test_kv_pages.py`` can property-test them as plain data
structures.

Why sharing preserves byte-identity: K/V bytes at position ``p`` depend
only on tokens ``[0..p]`` (causal masking, chunk-alignment invariance —
the property the chunked-prefill-vs-static tests already pin), so a
matched page holds exactly the bytes a fresh prefill would produce.  The
boundary chunk that straddles the shared/private line is *recomputed*:
rows below the shared length write back identical bytes (idempotent),
rows at or above it carry request-specific bytes and land only in the
copy-on-write / fresh pages the host mapped for them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from music_analyst_tpu.ops.kv_cache import KVCache
from music_analyst_tpu.ops.flash_attention import interpret_default
from music_analyst_tpu.ops.paged_attention import (
    PagedAttnView,
    check_stream_geometry,
)
from music_analyst_tpu.ops.quant import quantize_kv_page
from music_analyst_tpu.profiling.compile import profiled_jit

KV_QUANT_SCHEMES = ("none", "int8")


def _is_pow2(n: int) -> bool:
    return n >= 1 and not (n & (n - 1))


@dataclasses.dataclass
class QuantizedKVPages:
    """int8 page pool: codes + per-(page, row) f32 dequant scales.

    The quantized twin of the per-layer ``KVCache`` pool — same
    ``[n_pages + 1, page_size, n_kv, head_dim]`` geometry with int8
    codes, plus ``[n_pages + 1, page_size]`` scale planes for K and V
    (``ops/quant.quantize_kv_page``).  A registered pytree whose leaves
    ride along wherever the float pool's did, so page copy, free,
    checkpointing, and pin transfers move scales with their pages for
    free — the scheduler never special-cases quantization.
    """

    keys: jax.Array          # int8 [n_pages + 1, P, n_kv, D]
    values: jax.Array
    key_scale: jax.Array     # f32 [n_pages + 1, P]
    value_scale: jax.Array
    length: jax.Array        # int32 [n_slots] write offsets (bookkeeping)


jax.tree_util.register_dataclass(
    QuantizedKVPages,
    data_fields=["keys", "values", "key_scale", "value_scale", "length"],
    meta_fields=[],
)


@dataclasses.dataclass(frozen=True)
class PagePlan:
    """Static geometry of one paged runtime (compile-shape contract)."""

    n_slots: int        # pow2 — rows in the page table
    prefill_chunk: int  # tokens written per prefill dispatch
    prompt_region: int  # buffer rows for the prompt (multiple of chunk & page)
    max_new: int        # decode rows per slot (largest per-request budget)
    decode_span: int    # greedy steps per decode dispatch
    page_size: int      # pow2 — tokens per physical KV page
    n_pages: int        # allocatable pool size (excludes the trash page)

    def __post_init__(self):
        if not _is_pow2(self.n_slots):
            raise ValueError(f"n_slots must be a power of two, got {self.n_slots}")
        if not _is_pow2(self.page_size):
            raise ValueError(
                f"page_size must be a power of two, got {self.page_size}"
            )
        if self.prompt_region % self.prefill_chunk:
            raise ValueError(
                f"prompt_region ({self.prompt_region}) must be a multiple of "
                f"prefill_chunk ({self.prefill_chunk})"
            )
        if self.prompt_region % self.page_size:
            raise ValueError(
                f"prompt_region ({self.prompt_region}) must be a multiple of "
                f"page_size ({self.page_size})"
            )
        if self.max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {self.max_new}")
        if self.decode_span < 1:
            raise ValueError(f"decode_span must be >= 1, got {self.decode_span}")
        floor = max(self.n_slots, self.pages_per_slot)
        if self.n_pages < floor:
            raise ValueError(
                f"n_pages ({self.n_pages}) must be >= "
                f"max(n_slots, pages_per_slot) = {floor} — the pool must hold "
                "one page per slot and one full resident sequence"
            )

    @property
    def max_total(self) -> int:
        return self.prompt_region + self.max_new

    @property
    def prompt_pages(self) -> int:
        return self.prompt_region // self.page_size

    @property
    def decode_pages(self) -> int:
        return -(-self.max_new // self.page_size)

    @property
    def pages_per_slot(self) -> int:
        return self.prompt_pages + self.decode_pages

    @property
    def slot_span(self) -> int:
        """Gathered-view width: ``pages_per_slot * page_size`` rows — the
        model only ever sees the first ``max_total`` of them."""
        return self.pages_per_slot * self.page_size

    @property
    def trash_page(self) -> int:
        """Physical index of the write sink for free slots' table rows.

        The decode program writes a row for *every* slot (fixed shape); a
        freed slot's stale table row could otherwise scribble on pages
        that have since been recycled to another sequence.  Free rows
        point every entry here instead.  Never allocated, never read
        through an active mask."""
        return self.n_pages


class PagedDecodeRuntime:
    """Five-program continuous decode over a shared page pool.

    Holds no request state — the page table, refcounts, and the radix
    tree live in the host scheduler; this class owns only the compiled
    programs and the geometry they were traced for.  The page table /
    page row is a *traced* int32 operand, so table churn (sharing, CoW,
    eviction, slot reuse) never retraces.
    """

    def __init__(self, model, config, plan: PagePlan, eos_id: int,
                 mesh=None, kv_quant: str = "none") -> None:
        self.model = model
        self.config = config
        self.plan = plan
        self.eos_id = int(eos_id)
        # Mesh-aware mode (see SlotDecodeRuntime): the page pool's head
        # axis shards over tp per DECODE_KV_RULES; the page table stays a
        # replicated traced operand, so gather/scatter indices are shared
        # by every chip and only head-local bytes move.
        self.mesh = mesh
        if kv_quant not in KV_QUANT_SCHEMES:
            raise ValueError(
                f"kv_quant must be one of {KV_QUANT_SCHEMES}, got {kv_quant!r}"
            )
        self.kv_quant = kv_quant
        quantized = kv_quant == "int8"
        if not interpret_default():
            # Decode attends through the Mosaic-compiled streaming kernel;
            # a geometry it cannot lower is refused here, not at the
            # first dispatch.
            check_stream_geometry(
                config.n_kv_heads, config.dim // config.n_heads
            )
        # The dtype KV rows dequantize to (and the unquantized pool's
        # storage dtype): the model's activation dtype.
        compute_dtype = jnp.bfloat16
        self._compute_dtype = compute_dtype
        if plan.max_total > config.max_seq_len:
            raise ValueError(
                f"prompt_region + max_new ({plan.max_total}) exceeds the "
                f"model's max_seq_len ({config.max_seq_len})"
            )
        R = plan.prompt_region
        C = plan.prefill_chunk
        P = plan.page_size
        total = plan.max_total
        span = plan.slot_span
        pps = plan.pages_per_slot
        eos = jnp.asarray(self.eos_id, jnp.int32)
        # Pages a chunk write can straddle: C tokens starting at a multiple
        # of C touch at most one leading partial page + the full pages.
        # (Decode and verify no longer scatter — their writes land in the
        # pool row-by-row through the kernel-backed view.)
        n_wp_prefill = (C - 1) // P + 2

        def _view(c, row, length) -> KVCache:
            """Contiguous [B, max_total] view of the rows behind ``row``.

            ``row`` is ``[pps]`` (prefill, B=1) or ``[n_slots, pps]``
            (decode).  The view is sliced to exactly ``max_total`` rows so
            every downstream op — masks, softmax widths, reductions — is
            bit-identical to the monolithic runtime's buffer.  Prefill is
            the only remaining consumer (decode and verify read the pool
            through the fused kernel); for int8 the gathered codes
            dequantize here, so the chunk-prefill math runs on the same
            bf16 rows the kernel's load epilogue reconstructs.
            """
            keys = jnp.take(c.keys, row, axis=0)
            values = jnp.take(c.values, row, axis=0)
            if quantized:
                ks = jnp.take(c.key_scale, row, axis=0)[..., None, None]
                vs = jnp.take(c.value_scale, row, axis=0)[..., None, None]
                keys = (keys.astype(jnp.float32) * ks).astype(compute_dtype)
                values = (values.astype(jnp.float32) * vs).astype(
                    compute_dtype
                )
            if row.ndim == 1:
                shape = (1, span) + keys.shape[-2:]
            else:
                shape = (row.shape[0], span) + keys.shape[-2:]
            keys = keys.reshape(shape)[:, :total]
            values = values.reshape(shape)[:, :total]
            return KVCache(keys, values, length)

        def _attn_view(c, page_table, length) -> PagedAttnView:
            """The kernel-backed cache for decode/verify: binds the pool
            (+ scales), the table, and per-slot write offsets — no
            gathered copy."""
            return PagedAttnView(
                keys=c.keys, values=c.values,
                key_scale=c.key_scale if quantized else None,
                value_scale=c.value_scale if quantized else None,
                table=page_table, length=length,
                page_size=P, total=total,
            )

        def _repack(v: PagedAttnView, length):
            """Pool state back out of a scanned view (decode/verify write
            pages in place through the view, so the view IS the new
            pool)."""
            if quantized:
                return QuantizedKVPages(
                    v.keys, v.values, v.key_scale, v.value_scale, length
                )
            return KVCache(v.keys, v.values, length)

        def _pages(arr):
            """[B, max_total] view back to per-page layout [B, pps, P, ...],
            zero-padding the slack tail rows (>= max_total) — those rows
            are never attended, and deterministic zeros beat stale bytes."""
            pad = [(0, 0)] * arr.ndim
            pad[1] = (0, span - total)
            padded = jnp.pad(arr, pad)
            return padded.reshape(
                (arr.shape[0], pps, P) + arr.shape[2:]
            )

        def _prefill_chunk(params, caches, page_row, slot, chunk_ids, start,
                           length_after, last_index):
            """Write ``prefill_chunk`` prompt tokens through one slot's pages.

            Identical math to ``slots.prefill`` over the gathered view;
            the only paged part is the gather in and the per-page scatter
            out.  ``page_row``/``slot``/``start``/``length_after``/
            ``last_index`` are traced, so one program serves every slot,
            every page mapping, every chunk offset, every prompt length.
            The write-back covers every page the chunk touches; pages
            below a prefix hit's copy-on-write boundary only ever receive
            recomputed bytes identical to what they hold (see module
            docstring), so the scatter is idempotent there.
            """
            view = [_view(c, page_row, start) for c in caches]
            positions = (start + jnp.arange(C, dtype=jnp.int32))[None, :]
            q_pos = positions[:, :, None]
            kv_pos = jnp.arange(total, dtype=jnp.int32)[None, None, :]
            mask = (kv_pos <= q_pos)[:, None, :, :]
            logits, view = self.model.apply(
                {"params": params}, chunk_ids[None, :], positions, mask, view,
                last_position=last_index[None],
            )
            first = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)[0]
            lp0 = start // P
            new_caches = []
            for c, v in zip(caches, view):
                vk = _pages(v.keys)[0]    # [pps, P, n_kv, D]
                vv = _pages(v.values)[0]
                keys, values = c.keys, c.values
                if quantized:
                    key_scale, value_scale = c.key_scale, c.value_scale
                for j in range(n_wp_prefill):
                    lp = jnp.clip(lp0 + j, 0, pps - 1)
                    phys = page_row[lp]
                    pk = jax.lax.dynamic_slice_in_dim(vk, lp, 1, axis=0)
                    pv = jax.lax.dynamic_slice_in_dim(vv, lp, 1, axis=0)
                    if quantized:
                        # Quantize the page on the way out: per-row
                        # symmetric int8 + scale.  Rows the chunk only
                        # re-gathered (below ``start`` on the boundary
                        # page) round-trip through the bf16 view to
                        # within ±1 code, then sit at a fixed point of
                        # further rescatters — see
                        # ops/quant.quantize_kv_page.
                        pk, psk = quantize_kv_page(pk)
                        pv, psv = quantize_kv_page(pv)
                        key_scale = jax.lax.dynamic_update_slice(
                            key_scale, psk, (phys, 0)
                        )
                        value_scale = jax.lax.dynamic_update_slice(
                            value_scale, psv, (phys, 0)
                        )
                    keys = jax.lax.dynamic_update_slice(
                        keys, pk, (phys,) + (0,) * (keys.ndim - 1)
                    )
                    values = jax.lax.dynamic_update_slice(
                        values, pv, (phys,) + (0,) * (values.ndim - 1)
                    )
                length = c.length.at[slot].set(length_after)
                if quantized:
                    new_caches.append(QuantizedKVPages(
                        keys, values, key_scale, value_scale, length
                    ))
                else:
                    new_caches.append(KVCache(keys, values, length))
            return new_caches, first

        def _decode_step(params, caches, page_table, tokens, prompt_lens,
                         steps, budgets, done, active):
            """``decode_span`` greedy steps over all slots in one dispatch.

            The scan body is the same 1-wide step as ``slots.decode``,
            but the cache it threads is a :class:`PagedAttnView`: each
            step writes its new KV row straight into its physical page
            and attends through the fused kernel, so the scan carries
            the page *pool* itself — no gathered copy in, no page
            scatter out.  Write offsets sit at ``R + steps < total``, so
            every write lands in the slot's decode pages and shared
            prompt pages are never touched; free slots' table rows point
            at the trash page, which is never read through an active
            mask (and a free slot's own masked read of it is discarded
            by the ``adv`` select).
            """
            views = [_attn_view(c, page_table, c.length) for c in caches]
            kv_pos = jnp.arange(total, dtype=jnp.int32)[None, None, None, :]

            def body(carry, _):
                tokens, steps, done, views = carry
                adv = active & (steps < budgets)
                offsets = jnp.minimum(R + steps, total - 1)
                views_in = [
                    dataclasses.replace(v, length=offsets) for v in views
                ]
                pos = prompt_lens + steps
                prompt_part = kv_pos < prompt_lens[:, None, None, None]
                decode_part = (kv_pos >= R) & (
                    kv_pos - R <= steps[:, None, None, None]
                )
                step_mask = prompt_part | decode_part
                lg, views_out = self.model.apply(
                    {"params": params}, tokens[:, None], pos[:, None],
                    step_mask, views_in,
                )
                nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
                new_done = done | (tokens == eos)
                nxt = jnp.where(new_done, eos, nxt)
                out_tokens = jnp.where(adv, nxt, tokens)
                out_steps = jnp.where(adv, steps + 1, steps)
                out_done = jnp.where(adv, new_done, done)
                return (out_tokens, out_steps, out_done, views_out), tokens

            (tokens, steps, done, views), emitted = jax.lax.scan(
                body, (tokens, steps, done, views),
                None, length=plan.decode_span,
            )
            new_caches = [
                _repack(v, c.length) for c, v in zip(caches, views)
            ]
            return new_caches, tokens, steps, done, emitted

        def _verify_block(params, caches, page_table, tokens_blk, prompt_lens,
                          steps):
            """Score a ``[n_slots, K]`` drafted block in one dispatch.

            Identical semantics to ``slots.verify`` (column 0 = carry,
            columns 1.. = drafts; returns the greedy argmax after
            consuming each prefix — see ``kv_slots``): a teacher-forced
            scan of the *same* 1-wide kernel-backed step body as
            ``pages.decode``, because byte-identity demands the logits
            and written KV rows be bit-identical to plain decode (a
            K-wide scoring pass reduces in a different order and flips
            argmax near-ties).  Rejected drafts' rows stay in the decode
            pages but are never attended — the masks derive from the
            host-committed ``steps``, exactly as on the scatter path
            this replaces; shared prompt pages are never written (write
            offsets ``>= R``).
            """
            views = [_attn_view(c, page_table, c.length) for c in caches]
            kv_pos = jnp.arange(total, dtype=jnp.int32)[None, None, None, :]

            def body(carry, tok):
                views, steps = carry
                offsets = jnp.minimum(R + steps, total - 1)
                views_in = [
                    dataclasses.replace(v, length=offsets) for v in views
                ]
                pos = prompt_lens + steps
                prompt_part = kv_pos < prompt_lens[:, None, None, None]
                decode_part = (kv_pos >= R) & (
                    kv_pos - R <= steps[:, None, None, None]
                )
                step_mask = prompt_part | decode_part
                lg, views_out = self.model.apply(
                    {"params": params}, tok[:, None], pos[:, None],
                    step_mask, views_in,
                )
                nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
                return (views_out, steps + 1), nxt

            (views, _), preds = jax.lax.scan(
                body, (views, steps), tokens_blk.T,
            )
            preds = preds.T                           # [n, K]
            new_caches = [
                _repack(v, c.length) for c, v in zip(caches, views)
            ]
            return new_caches, preds

        def _free_pages(caches, page_mask, slot_mask):
            """Zero a mask of physical pages and reset masked slots'
            lengths — the failure-path hard isolation.  Normal completion
            is host-only (unpin + table row → trash): the prefill/decode
            masks and write offsets already keep stale pages unreachable.
            For int8 the scale rows zero with their pages (a zero scale
            dequantizes zero codes to exact zeros).
            """
            row = page_mask[:, None, None, None]
            new_caches = []
            for c in caches:
                keys = jnp.where(row, jnp.zeros((), c.keys.dtype), c.keys)
                values = jnp.where(
                    row, jnp.zeros((), c.values.dtype), c.values
                )
                length = jnp.where(slot_mask, 0, c.length)
                if quantized:
                    srow = page_mask[:, None]
                    new_caches.append(QuantizedKVPages(
                        keys, values,
                        jnp.where(srow, 0.0, c.key_scale),
                        jnp.where(srow, 0.0, c.value_scale),
                        length,
                    ))
                else:
                    new_caches.append(KVCache(keys, values, length))
            return new_caches

        def _copy_page(caches, src, dst):
            """Copy one physical page ``src → dst`` across every layer —
            the copy-on-write for a prefix hit's partially-filled boundary
            page: the new occupant overwrites its suffix rows in the copy
            while the original keeps serving other sequences.  int8 pages
            carry their scale rows along."""

            def move(buf):
                page = jax.lax.dynamic_slice_in_dim(buf, src, 1, axis=0)
                return jax.lax.dynamic_update_slice(
                    buf, page, (dst,) + (0,) * (buf.ndim - 1)
                )

            new_caches = []
            for c in caches:
                if quantized:
                    new_caches.append(QuantizedKVPages(
                        move(c.keys), move(c.values),
                        move(c.key_scale), move(c.value_scale), c.length,
                    ))
                else:
                    new_caches.append(
                        KVCache(move(c.keys), move(c.values), c.length)
                    )
            return new_caches

        self.prefill_chunk = profiled_jit(_prefill_chunk, name="pages.prefill")
        self.decode_step = profiled_jit(_decode_step, name="pages.decode")
        self.verify_block = profiled_jit(_verify_block, name="pages.verify")
        self.free_pages = profiled_jit(_free_pages, name="pages.free")
        self.copy_page = profiled_jit(_copy_page, name="pages.copy")

    # ---------------------------------------------------------------- state

    def init_caches(self, dtype=jnp.bfloat16) -> List[Any]:
        """Fresh page pool: ``[n_pages + 1, page_size, n_kv, head_dim]``
        per layer (the +1 row is the trash page) with the monolithic
        runtime's per-slot write-offset ``length`` kept for bookkeeping.
        ``kv_quant="int8"`` pools store int8 codes plus per-(page, row)
        f32 scale planes (:class:`QuantizedKVPages`)."""
        cfg = self.config
        head_dim = cfg.dim // cfg.n_heads
        plan = self.plan
        shape = (plan.n_pages + 1, plan.page_size, cfg.n_kv_heads, head_dim)
        if self.kv_quant == "int8":
            sshape = (plan.n_pages + 1, plan.page_size)
            caches = [
                QuantizedKVPages(
                    keys=jnp.zeros(shape, jnp.int8),
                    values=jnp.zeros(shape, jnp.int8),
                    key_scale=jnp.zeros(sshape, jnp.float32),
                    value_scale=jnp.zeros(sshape, jnp.float32),
                    length=jnp.zeros((plan.n_slots,), jnp.int32),
                )
                for _ in range(cfg.n_layers)
            ]
        else:
            caches = [
                KVCache(
                    keys=jnp.zeros(shape, dtype),
                    values=jnp.zeros(shape, dtype),
                    length=jnp.zeros((plan.n_slots,), jnp.int32),
                )
                for _ in range(cfg.n_layers)
            ]
        if self.mesh is not None:
            from music_analyst_tpu.parallel.sharding import shard_kv_caches

            caches = shard_kv_caches(caches, self.mesh, cfg.n_kv_heads)
        return caches

    def kv_token_bytes(self, dtype=jnp.bfloat16) -> int:
        """HBM bytes one cached token costs across all layers (K + V).

        Quantization-aware: under ``kv_quant="int8"`` a token stores
        int8 codes plus its share of the per-(page, row) f32 scales —
        one 4-byte scale per token for K and one for V, per layer."""
        cfg = self.config
        head_dim = cfg.dim // cfg.n_heads
        if self.kv_quant == "int8":
            return 2 * cfg.n_layers * (cfg.n_kv_heads * head_dim + 4)
        itemsize = jnp.zeros((), dtype).dtype.itemsize
        return 2 * cfg.n_layers * cfg.n_kv_heads * head_dim * itemsize

    def kv_token_bytes_unquantized(self, dtype=jnp.bfloat16) -> int:
        """What the same token would cost without KV quantization — the
        baseline for the manifest's ``kv_quant.bytes_saved``."""
        cfg = self.config
        head_dim = cfg.dim // cfg.n_heads
        itemsize = jnp.zeros((), dtype).dtype.itemsize
        return 2 * cfg.n_layers * cfg.n_kv_heads * head_dim * itemsize

    def page_bytes(self, dtype=jnp.bfloat16) -> int:
        return self.plan.page_size * self.kv_token_bytes(dtype)

    def pool_bytes(self, dtype=jnp.bfloat16) -> int:
        """Whole-pool HBM footprint across layers (incl. the trash page)."""
        return (self.plan.n_pages + 1) * self.page_bytes(dtype)

    def compiled_variants(self) -> int:
        """Total compiled-program count across the five programs — the
        zero-retrace assertion reads this before/after page-table churn."""
        return sum(
            fn._cache_size()
            for fn in (self.prefill_chunk, self.decode_step, self.verify_block,
                       self.free_pages, self.copy_page)
        )

    def prompt_chunks(self, n_tokens: int) -> Sequence[int]:
        """Chunk start offsets covering a prompt of ``n_tokens`` tokens."""
        n = max(1, min(int(n_tokens), self.plan.prompt_region))
        C = self.plan.prefill_chunk
        return range(0, ((n + C - 1) // C) * C, C)


# ====================================================================== host
# Pure-Python page accounting + radix tree (no jax imports at runtime) —
# the scheduler drives these; tests/test_kv_pages.py property-tests them.


class PagePool:
    """Free list + refcounts over the physical pages of one pool.

    A page is *free* iff no slot maps it (``slot_refs == 0``) and the
    radix index doesn't hold it (``in_tree`` false).  ``alloc`` hands out
    free pages (unpinned — the caller pins them as it maps them);
    releasing the last reference returns the page to the free list.
    """

    def __init__(self, n_pages: int) -> None:
        self.n_pages = int(n_pages)
        self.slot_refs = [0] * self.n_pages
        self.in_tree = [False] * self.n_pages
        # Pop from the tail → pages are handed out in ascending order
        # (deterministic layouts; nice for debugging dumps).
        self._free = list(range(self.n_pages - 1, -1, -1))

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self, k: int) -> Optional[List[int]]:
        if k > len(self._free):
            return None
        return [self._free.pop() for _ in range(k)]

    def pin(self, phys: int) -> None:
        self.slot_refs[phys] += 1

    def unpin(self, phys: int) -> None:
        refs = self.slot_refs[phys] - 1
        if refs < 0:
            raise ValueError(f"unpin of unpinned page {phys}")
        self.slot_refs[phys] = refs
        self._maybe_free(phys)

    def pin_row(self, pages: Sequence[int]) -> None:
        """Pin every page of one table row — a checkpoint taking its own
        reference so the row survives the slot's release (and the zeroing
        failure path, which only touches fully-unreferenced pages)."""
        for phys in pages:
            self.pin(phys)

    def unpin_row(self, pages: Sequence[int]) -> None:
        """Release one reference from every page of a table row."""
        for phys in pages:
            self.unpin(phys)

    def tree_add(self, phys: int) -> None:
        if self.in_tree[phys]:
            raise ValueError(f"page {phys} already in the radix index")
        self.in_tree[phys] = True

    def tree_drop(self, phys: int) -> None:
        if not self.in_tree[phys]:
            raise ValueError(f"page {phys} not in the radix index")
        self.in_tree[phys] = False
        self._maybe_free(phys)

    def _maybe_free(self, phys: int) -> None:
        if self.slot_refs[phys] == 0 and not self.in_tree[phys]:
            self._free.append(phys)

    def check(self) -> None:
        """Invariant audit (tests): the free list is exactly the
        unreferenced pages, with no duplicates."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise AssertionError("duplicate pages in the free list")
        for p in range(self.n_pages):
            should_be_free = self.slot_refs[p] == 0 and not self.in_tree[p]
            if should_be_free != (p in free):
                raise AssertionError(
                    f"page {p}: refs={self.slot_refs[p]} "
                    f"in_tree={self.in_tree[p]} free={p in free}"
                )


class _RadixNode:
    __slots__ = ("tokens", "phys", "children", "parent", "last_used")

    def __init__(self, tokens: Tuple[int, ...], phys: Optional[int],
                 parent: Optional["_RadixNode"]) -> None:
        self.tokens = tokens          # the page's *valid* tokens
        self.phys = phys              # physical page (None only at root)
        self.children: Dict[Tuple[int, ...], "_RadixNode"] = {}
        self.parent = parent
        self.last_used = 0

    @property
    def n_valid(self) -> int:
        return len(self.tokens)


@dataclasses.dataclass
class PrefixMatch:
    """Result of a radix lookup for one prompt."""

    pages: List[int]          # full shared pages, in slot-local order
    full_tokens: int          # len(pages) * page_size
    partial_phys: Optional[int]  # boundary page to copy-on-write (or None)
    partial_tokens: int       # tokens matched inside the boundary page

    @property
    def tokens(self) -> int:
        return self.full_tokens + self.partial_tokens


class RadixIndex:
    """Radix tree over page-granular token runs.

    Nodes are pages: a child is keyed by its page's valid-token tuple
    (full pages have exactly ``page_size`` tokens; a leaf may be partial).
    Only full pages extend the path — a partial page can't be followed by
    an aligned successor.  ``match`` walks exact full-page children, then
    takes the longest-common-prefix partial at the frontier; ``insert``
    adds the pages of a completed prefill (the pool takes an ``in_tree``
    reference per adopted page); ``evict`` drops least-recently-used
    *leaves* whose pages no slot maps — a pinned page is never evicted.
    """

    def __init__(self, page_size: int) -> None:
        if not _is_pow2(page_size):
            raise ValueError(f"page_size must be a power of two, got {page_size}")
        self.page_size = int(page_size)
        self.root = _RadixNode((), None, None)
        self._clock = 0

    def _touch(self, node: _RadixNode) -> None:
        self._clock += 1
        while node is not None and node is not self.root:
            node.last_used = self._clock
            node = node.parent

    def match(self, ids: Sequence[int]) -> PrefixMatch:
        """Longest cached prefix of ``ids``: whole pages while they match
        exactly, then the best partial page at the frontier.  Never
        returns more than ``len(ids)`` tokens (so a fully-cached prompt
        still re-runs its final chunk for the first-token logits)."""
        ids = [int(t) for t in ids]
        P = self.page_size
        node = self.root
        pages: List[int] = []
        i = 0
        while len(ids) - i >= P:
            child = node.children.get(tuple(ids[i:i + P]))
            if child is None or child.n_valid != P:
                break
            pages.append(child.phys)
            node = child
            i += P
        best: Optional[_RadixNode] = None
        best_k = 0
        remaining = ids[i:]
        if remaining:
            for child in node.children.values():
                k = 0
                for a, b in zip(child.tokens, remaining):
                    if a != b:
                        break
                    k += 1
                if k > best_k:
                    best, best_k = child, k
        if pages or best is not None:
            self._touch(best if best is not None else node)
        if node is not self.root:
            self._touch(node)
        return PrefixMatch(
            pages=pages,
            full_tokens=i,
            partial_phys=best.phys if best is not None else None,
            partial_tokens=best_k,
        )

    def insert(self, ids: Sequence[int], phys_pages: Sequence[int],
               pool: PagePool) -> int:
        """Adopt the pages of one completed prefill into the tree.

        ``ids`` are the prompt's real tokens (length ``plen``);
        ``phys_pages`` is the slot's table row.  Pages already present
        (same valid-token run at the same depth) are left alone — the
        slot's private duplicate simply isn't adopted and frees on
        completion.  Returns the number of pages adopted."""
        ids = [int(t) for t in ids]
        P = self.page_size
        n_full, rem = divmod(len(ids), P)
        node = self.root
        adopted = 0
        for pi in range(n_full):
            seg = tuple(ids[pi * P:(pi + 1) * P])
            child = node.children.get(seg)
            if child is None:
                child = _RadixNode(seg, int(phys_pages[pi]), node)
                node.children[seg] = child
                pool.tree_add(child.phys)
                adopted += 1
            node = child
        if rem:
            seg = tuple(ids[n_full * P:n_full * P + rem])
            if seg not in node.children:
                child = _RadixNode(seg, int(phys_pages[n_full]), node)
                node.children[seg] = child
                pool.tree_add(child.phys)
                adopted += 1
        if node is not self.root or adopted:
            self._touch(node)
        return adopted

    def _leaves(self) -> List[_RadixNode]:
        out: List[_RadixNode] = []
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            if node.children:
                stack.extend(node.children.values())
            else:
                out.append(node)
        return out

    def evict(self, pool: PagePool, need: int) -> int:
        """Free at least ``need`` pages by dropping cold unpinned leaves
        (LRU by ``last_used``); evicting a leaf may expose its parent as
        the next candidate.  Returns how many pages were actually freed —
        fewer than ``need`` iff everything left is pinned."""
        freed = 0
        while freed < need:
            candidates = [
                leaf for leaf in self._leaves()
                if pool.slot_refs[leaf.phys] == 0
            ]
            if not candidates:
                break
            victim = min(candidates, key=lambda n: n.last_used)
            del victim.parent.children[victim.tokens]
            pool.tree_drop(victim.phys)
            freed += 1
        return freed

    def page_count(self) -> int:
        n = 0
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            n += 1
            stack.extend(node.children.values())
        return n

    # Occupancy accessors (engine ledger): tree size without exposing
    # internals.  Nodes are pages, so node_count == page_count; kept as
    # a named alias because the ledger reports both dimensions.
    def node_count(self) -> int:
        return self.page_count()

    def token_count(self) -> int:
        """Valid tokens held by the tree — the pinned KV the index keeps
        resident on behalf of future prefix hits."""
        n = 0
        stack = list(self.root.children.values())
        while stack:
            node = stack.pop()
            n += node.n_valid
            stack.extend(node.children.values())
        return n
