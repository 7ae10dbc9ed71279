"""Slot-indexed KV cache runtime for continuous-batching decode.

The static generation path (``models/llama.py:_generate_scan``) pads every
prompt to the batch's longest and decodes all rows to the batch's largest
token budget — a late arrival waits for the whole batch.  This module is
the device half of the continuous-batching runtime: ``n_slots`` (pow2)
independent sequences live side by side in one slot-indexed KV cache, and
a handful of **fixed-shape compiled programs** move them forward.  Slots
are claimed and freed by the host scheduler (``serving/decode_loop.py``)
between dispatches; no program ever retraces as requests come and go:

* **chunked prefill** — a prompt is written into a free slot's cache in
  fixed-size token chunks (one compiled program reused for every prompt
  length, bounding the latency spike a long prompt injects between decode
  steps);
* **decode step** — ``decode_span`` greedy steps over *all* slots in one
  dispatch, with per-slot positions and an active-mask; inactive slots are
  masked out of attention and their outputs discarded;
* **slot free** — a slot's cache rows and lengths are zeroed.  Normal
  completion frees host-side only (the prefill/decode masks and write
  offsets already guarantee a new occupant never attends stale KV); this
  program is the failure-path hard isolation — after a poisoned request
  nothing about the slot's contents is trusted.
* **slot snapshot / restore** — copy one slot's KV rows into stand-alone
  device buffers and write them back into any (possibly different) free
  slot.  This is the monolithic backend's O(1) preempt-resume: a
  checkpointed victim re-enters decode without re-running a single
  prefill chunk (the paged backend gets the same for free — its
  checkpoint is a pinned page-table row).

Bit-exactness contract: the cache layout deliberately mirrors the static
path's slot/position split — the prompt occupies buffer rows
``[0, prompt_region)`` and decode token ``t`` sits at *buffer slot*
``prompt_region + t`` while carrying *RoPE position* ``prompt_len + t``,
with the identical ``prompt_part | decode_part`` mask.  When
``prompt_region`` equals the static path's padded prompt width (and so
``max_total`` equals its KV width), every per-row attention reduction sees
the same values at the same buffer indices, making continuous greedy
tokens byte-identical to ``generate_batch`` (asserted by
``tests/test_continuous.py`` and the ``continuous`` bench suite).

All three programs go through :func:`profiled_jit`, so the recompile
detector (``profiling.recompiles``) is the zero-retrace witness.

This monolithic per-slot layout is the ``page_size=0`` escape hatch of
the serving stack: the default backend is the prefix-shared *paged*
runtime (``ops/kv_pages.py``), which keeps the same scheduler, the same
bit-exactness contract, and the same fixed-program discipline but stores
KV in a pooled page table so requests sharing a prompt prefix share
physical pages.  Since ISSUE 18 the paged decode path reads the pool
through a fused Pallas kernel (``ops/paged_attention.py``) that walks
the page table in place — the gather/scatter materialization that once
made decode-heavy no-overlap workloads a reason to pin this backend is
retired, and the ``continuous`` suite's kernel A/B measures paged
against this cache directly.  ``--page-size 0`` remains supported as
the A/B baseline and as the fallback if the kernel path ever needs to
be ruled out.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from music_analyst_tpu.ops.kv_cache import KVCache
from music_analyst_tpu.profiling.compile import profiled_jit


@dataclasses.dataclass(frozen=True)
class SlotPlan:
    """Static geometry of one slot runtime (compile-shape contract)."""

    n_slots: int        # pow2 — rows in the slot cache
    prefill_chunk: int  # tokens written per prefill dispatch
    prompt_region: int  # buffer rows reserved for the prompt (multiple of chunk)
    max_new: int        # decode rows per slot (largest per-request budget)
    decode_span: int    # greedy steps per decode dispatch

    def __post_init__(self):
        if self.n_slots < 1 or (self.n_slots & (self.n_slots - 1)):
            raise ValueError(f"n_slots must be a power of two, got {self.n_slots}")
        if self.prompt_region % self.prefill_chunk:
            raise ValueError(
                f"prompt_region ({self.prompt_region}) must be a multiple of "
                f"prefill_chunk ({self.prefill_chunk})"
            )
        if self.max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {self.max_new}")
        if self.decode_span < 1:
            raise ValueError(f"decode_span must be >= 1, got {self.decode_span}")

    @property
    def max_total(self) -> int:
        return self.prompt_region + self.max_new


class SlotDecodeRuntime:
    """Three-program continuous decode over a slot-indexed KV cache.

    Holds no request state — slots, budgets, and arrival order live in the
    host scheduler; this class owns only the compiled programs and the
    geometry they were traced for.  ``params`` is an explicit argument to
    every program so residency reloads / weight-quantized trees flow
    through without retracing.
    """

    def __init__(self, model, config, plan: SlotPlan, eos_id: int,
                 mesh=None) -> None:
        self.model = model
        self.config = config
        self.plan = plan
        self.eos_id = int(eos_id)
        # Mesh-aware mode: params arrive already placed by the classifier's
        # TP_RULES and the cache is placed by DECODE_KV_RULES (head axis
        # over tp), so the three programs lower once per geometry with
        # GSPMD-propagated shardings — same zero-retrace discipline, same
        # bytes (tp just splits the head loop the reductions never cross).
        self.mesh = mesh
        if plan.max_total > config.max_seq_len:
            raise ValueError(
                f"prompt_region + max_new ({plan.max_total}) exceeds the "
                f"model's max_seq_len ({config.max_seq_len})"
            )
        R = plan.prompt_region
        C = plan.prefill_chunk
        total = plan.max_total
        eos = jnp.asarray(self.eos_id, jnp.int32)

        def _prefill_chunk(params, caches, slot, chunk_ids, start, length_after,
                           last_index):
            """Write ``prefill_chunk`` prompt tokens into one slot's cache.

            ``slot``/``start``/``length_after``/``last_index`` are traced
            int32 scalars, so one compiled program serves every slot, every
            chunk offset, and every prompt length.  ``last_index`` is the
            chunk-local index of the prompt's final token (only meaningful
            on the last chunk; earlier chunks return a throwaway token).
            """
            # Batch-1 view of the slot's rows, scalar length = this chunk's
            # write offset — KVCache.update then lands the chunk at
            # positions [start, start + C).
            view = [
                KVCache(
                    jax.lax.dynamic_slice_in_dim(c.keys, slot, 1, axis=0),
                    jax.lax.dynamic_slice_in_dim(c.values, slot, 1, axis=0),
                    start,
                )
                for c in caches
            ]
            positions = (start + jnp.arange(C, dtype=jnp.int32))[None, :]
            # Causal over the global offsets: a real prompt token at global
            # position p attends exactly [0, p] — chunk padding (tokens past
            # the prompt's end) sits at positions > p and is causally
            # unreachable, so no explicit padding mask is needed.
            q_pos = positions[:, :, None]                     # [1, C, 1]
            kv_pos = jnp.arange(total, dtype=jnp.int32)[None, None, :]
            mask = (kv_pos <= q_pos)[:, None, :, :]           # [1, 1, C, total]
            logits, view = self.model.apply(
                {"params": params}, chunk_ids[None, :], positions, mask, view,
                last_position=last_index[None],
            )
            first = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)[0]
            new_caches = []
            for c, v in zip(caches, view):
                keys = jax.lax.dynamic_update_slice(
                    c.keys, v.keys, (slot, 0, 0, 0)
                )
                values = jax.lax.dynamic_update_slice(
                    c.values, v.values, (slot, 0, 0, 0)
                )
                new_caches.append(
                    KVCache(keys, values, c.length.at[slot].set(length_after))
                )
            return new_caches, first

        def _decode_step(params, caches, tokens, prompt_lens, steps, budgets,
                         done, active):
            """``decode_span`` greedy steps over all slots in one dispatch.

            Mirrors ``_generate_scan``'s per-row semantics exactly: token
            ``t`` occupies buffer slot ``R + t`` with RoPE position
            ``prompt_len + t`` under the ``prompt_part | decode_part`` mask,
            and rows that already emitted EOS keep emitting EOS.  A slot
            advances only while ``active`` and under budget; frozen/free
            rows still write (fixed shape) but only into their own dead
            tail, which the masks — and the zeroing free program — keep
            unreachable.
            """
            kv_pos = jnp.arange(total, dtype=jnp.int32)[None, None, None, :]

            def body(carry, _):
                tokens, steps, done, caches = carry
                adv = active & (steps < budgets)
                # Clamp the write offset so a frozen row's dead-tail write
                # can only land on its own last (already-consumed) row.
                offsets = jnp.minimum(R + steps, total - 1)
                caches_in = [
                    KVCache(c.keys, c.values, offsets) for c in caches
                ]
                pos = prompt_lens + steps                     # [n_slots]
                prompt_part = kv_pos < prompt_lens[:, None, None, None]
                decode_part = (kv_pos >= R) & (
                    kv_pos - R <= steps[:, None, None, None]
                )
                step_mask = prompt_part | decode_part
                lg, caches_out = self.model.apply(
                    {"params": params}, tokens[:, None], pos[:, None],
                    step_mask, caches_in,
                )
                nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
                new_done = done | (tokens == eos)
                nxt = jnp.where(new_done, eos, nxt)
                out_tokens = jnp.where(adv, nxt, tokens)
                out_steps = jnp.where(adv, steps + 1, steps)
                out_done = jnp.where(adv, new_done, done)
                return (out_tokens, out_steps, out_done, caches_out), tokens

            (tokens, steps, done, caches), emitted = jax.lax.scan(
                body, (tokens, steps, done, caches),
                None, length=plan.decode_span,
            )
            return caches, tokens, steps, done, emitted  # emitted [span, n]

        def _verify_block(params, caches, tokens_blk, prompt_lens, steps):
            """Score a ``[n_slots, K]`` drafted block in one dispatch.

            Column 0 of ``tokens_blk`` is each slot's pending carry token
            and columns ``1..K-1`` are host-proposed drafts.  Returns the
            greedy argmax after consuming ``tokens_blk[:, :t+1]`` for every
            ``t`` — the host compares drafts against these predictions to
            find the longest accepted prefix (``serving/decode_loop.py``).

            The block is executed as a teacher-forced scan of the *same*
            1-wide step body as ``_decode_step`` (drafted tokens in place
            of argmax feedback).  Byte-identity demands this: a K-wide
            parallel scoring pass reduces its attention and KV projections
            in a different summation order, and the last-bit bf16/fp32
            differences in the written KV rows (and the logits) flip
            greedy argmax near-ties — observed on CPU with tiny models.
            Scanning keeps every logit and every committed KV row
            bit-identical to plain decode while still amortising K tokens
            into ONE dispatch (one host round trip, one program).

            Rejected-suffix rows are written but never read: each step's
            mask exposes rows ``<= R + steps + t`` only, and the next
            dispatch — verify or plain — starts at most ``K-1`` rows back
            and overwrites them before exposing them.  Host state
            (budgets, EOS latch, active gating) stays host-side;
            non-participating slots' writes land in their own dead tail.
            """
            kv_pos = jnp.arange(total, dtype=jnp.int32)[None, None, None, :]

            def body(carry, tok):
                caches, steps = carry
                offsets = jnp.minimum(R + steps, total - 1)
                caches_in = [
                    KVCache(c.keys, c.values, offsets) for c in caches
                ]
                pos = prompt_lens + steps                 # [n_slots]
                prompt_part = kv_pos < prompt_lens[:, None, None, None]
                decode_part = (kv_pos >= R) & (
                    kv_pos - R <= steps[:, None, None, None]
                )
                step_mask = prompt_part | decode_part
                lg, caches_out = self.model.apply(
                    {"params": params}, tok[:, None], pos[:, None],
                    step_mask, caches_in,
                )
                nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
                return (caches_out, steps + 1), nxt

            (caches, _), preds = jax.lax.scan(
                body, (caches, steps), tokens_blk.T,
            )
            return caches, preds.T                        # [n, K]

        def _snapshot_slot(caches, slot):
            """Copy one slot's KV rows (every layer) and its write offset
            into stand-alone device buffers — the checkpoint half of O(1)
            preempt-resume (``serving/decode_loop.py``).

            ``slot`` is a traced int32 scalar, so one compiled program
            snapshots any slot.  The stacked ``[n_layers, max_total, ...]``
            result lives on device until restored (or dropped), never
            crossing to the host: checkpointing costs one device-side copy,
            not a readback.
            """
            keys = jnp.stack([
                jax.lax.dynamic_slice_in_dim(c.keys, slot, 1, axis=0)[0]
                for c in caches
            ])
            values = jnp.stack([
                jax.lax.dynamic_slice_in_dim(c.values, slot, 1, axis=0)[0]
                for c in caches
            ])
            length = jax.lax.dynamic_slice_in_dim(
                caches[0].length, slot, 1, axis=0
            )[0]
            return keys, values, length

        def _restore_slot(caches, keys, values, slot, length):
            """Write a snapshot back into (any) slot's rows — the restore
            half of O(1) resume.  The buffer layout is identical across
            slots and RoPE is already baked into the stored K/V bytes, so
            a snapshot taken from one slot index replays byte-identically
            from another.
            """
            new_caches = []
            for li, c in enumerate(caches):
                k = jax.lax.dynamic_update_slice(
                    c.keys, keys[li][None], (slot, 0, 0, 0)
                )
                v = jax.lax.dynamic_update_slice(
                    c.values, values[li][None], (slot, 0, 0, 0)
                )
                new_caches.append(
                    KVCache(k, v, c.length.at[slot].set(length))
                )
            return new_caches

        def _free_slots(caches, free_mask):
            """Zero freed slots' KV rows and reset their write offsets.

            The masks already make a freed slot's stale KV unreachable, so
            the scheduler only runs this on failure paths (poisoned
            request, persistent decode error), where the invariants behind
            that argument are themselves suspect.
            """
            row = free_mask[:, None, None, None]
            return [
                KVCache(
                    jnp.where(row, jnp.zeros((), c.keys.dtype), c.keys),
                    jnp.where(row, jnp.zeros((), c.values.dtype), c.values),
                    jnp.where(free_mask, 0, c.length),
                )
                for c in caches
            ]

        self.prefill_chunk = profiled_jit(_prefill_chunk, name="slots.prefill")
        self.decode_step = profiled_jit(_decode_step, name="slots.decode")
        self.verify_block = profiled_jit(_verify_block, name="slots.verify")
        self.free_slots = profiled_jit(_free_slots, name="slots.free")
        self.snapshot_slot = profiled_jit(_snapshot_slot, name="slots.snapshot")
        self.restore_slot = profiled_jit(_restore_slot, name="slots.restore")

    # ---------------------------------------------------------------- state

    def init_caches(self, dtype=jnp.bfloat16) -> List[KVCache]:
        """Fresh all-slots cache: ``[n_slots, max_total, n_kv, head_dim]``
        per layer with a per-slot (vector) write-offset ``length``."""
        cfg = self.config
        head_dim = cfg.dim // cfg.n_heads
        plan = self.plan
        caches = [
            KVCache(
                keys=jnp.zeros(
                    (plan.n_slots, plan.max_total, cfg.n_kv_heads, head_dim),
                    dtype,
                ),
                values=jnp.zeros(
                    (plan.n_slots, plan.max_total, cfg.n_kv_heads, head_dim),
                    dtype,
                ),
                length=jnp.zeros((plan.n_slots,), jnp.int32),
            )
            for _ in range(cfg.n_layers)
        ]
        if self.mesh is not None:
            from music_analyst_tpu.parallel.sharding import shard_kv_caches

            caches = shard_kv_caches(caches, self.mesh, cfg.n_kv_heads)
        return caches

    def kv_bytes(self, dtype=jnp.bfloat16) -> int:
        """Resident KV bytes of the monolithic all-slots cache — the
        engine ledger's occupancy counterpart of the paged runtime's
        ``pool_bytes`` (keys + values across every layer and slot)."""
        cfg = self.config
        head_dim = cfg.dim // cfg.n_heads
        itemsize = jnp.zeros((), dtype).dtype.itemsize
        return (
            cfg.n_layers * 2 * self.plan.n_slots * self.plan.max_total
            * cfg.n_kv_heads * head_dim * itemsize
        )

    def compiled_variants(self) -> int:
        """Total compiled-program count across the six programs — the
        zero-retrace assertion reads this before/after a workload."""
        return sum(
            fn._cache_size()
            for fn in (self.prefill_chunk, self.decode_step, self.verify_block,
                       self.free_slots, self.snapshot_slot, self.restore_slot)
        )

    def prompt_chunks(self, n_tokens: int) -> Sequence[int]:
        """Chunk start offsets covering a prompt of ``n_tokens`` tokens."""
        n = max(1, min(int(n_tokens), self.plan.prompt_region))
        C = self.plan.prefill_chunk
        return range(0, ((n + C - 1) // C) * C, C)
