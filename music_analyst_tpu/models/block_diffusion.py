"""Generation by diffusion over blocks, for a decoder whose configuration
says ``generation: block_diffusion`` (``models/llama.LlamaConfig``).

Such a model does not yield one token a row a step.  New tokens come a
block of ``block_length`` positions at a time: a block starts as ``[clean
..., MASK ...]`` (the clean head is what the prompt left of its last,
partial block), and a **denoising pass** runs the block's positions over
the cache, bidirectional among themselves, writing nothing; at each masked
position the token is the argmax and its confidence the softmax
probability of it; the pass unmasks every masked position whose confidence
exceeds ``confidence_threshold`` and at least ``block_length /
denoising_steps`` of them, the most confident (SDAR's sampler, greedy).  A
token never changes once unmasked.  When no mask is left a **commit pass**
runs the clean block once more and writes its keys and values
(``ops/kv_cache.BlockPass``).

A step is two jitted programs dispatched back to back with no host sync
between them, so that a trace names them apart:

* :func:`diffusion_prefill_program` (``llama_diffusion_prefill``): the
  prompts' whole blocks under the block-causal mask
  (``ops/kv_cache.BlockCausalPrefill``), no logits (a diffusion prefill has
  no next token to read), the hidden state on the whole blocks' positions
  alone from the embedding to the last layer where
  ``models/compact_stream.py`` says so (the compact token stream; keys and
  values put back at ``[B, S]`` for the cache); returns the caches, zeros
  at and behind a row's whole blocks;
* :func:`diffusion_denoise_program` (``llama_diffusion_denoise``): takes
  the caches (donated) and runs every block on the device, a ``lax.scan``
  over blocks around a ``lax.while_loop`` over passes: no host round trip
  a pass.

:class:`BlockDiffusionClassifier` runs them behind the staged hooks every
backend has, and reads the label as the reference does: generate a reply,
normalise its first word.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from music_analyst_tpu.models.compact_stream import stream_slots
from music_analyst_tpu.models.llama import (
    LlamaConfig,
    LlamaModel,
    LlamaZeroShotClassifier,
    _expert_id_dtype,
    _expert_ids,
    _partitioned,
    _prefill_capacity,
    _routing_stats,
    _sown_by_layer,
    init_caches,
)
from music_analyst_tpu.ops.kv_cache import BlockCausalPrefill, BlockPass
from music_analyst_tpu.profiling.compile import profiled_jit
from music_analyst_tpu.utils.labels import SUPPORTED_LABELS, normalise_label

# What ``generate`` and the classifier ask for when the caller names no
# budget: the reference's 16 new tokens.
MAX_NEW_TOKENS = 16

BLOCK_STEP_REFUSAL = (
    "this model generates by diffusion over blocks: a step yields between "
    "one and block_length tokens a row and a last pass commits the block's "
    "keys and values; the {runtime} runtime assumes a step that yields one "
    "token a row (serving/decode_loop.py, ops/kv_slots.py, ops/kv_pages.py)"
)


def unmask(logits: jax.Array, tokens: jax.Array, masked: jax.Array,
           threshold: float, at_least: int):
    """One pass of the sampler on ``logits [B, n, V]`` of a block whose
    positions hold ``tokens [B, n]``, ``masked [B, n]`` of them still the
    mask: every masked position whose confidence (the softmax probability
    of its argmax) exceeds ``threshold`` is unmasked, and the ``at_least``
    most confident of the masked ones whatever their confidence (ties to
    the lower position).  Returns ``(tokens, unmasked [B, n] bool, logp [B,
    n])``: the block after the pass (an unmasked token is its position's
    argmax; every other position keeps what it held), which positions this
    pass unmasked, and the log-probability of each position's argmax."""
    logits = logits.astype(jnp.float32)
    best = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
    logp = logits.max(axis=-1) - jax.nn.logsumexp(logits, axis=-1)
    confidence = jnp.where(masked, jnp.exp(logp), -1.0)
    order = jnp.argsort(-confidence, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    unmasked = masked & ((confidence > threshold) | (rank < at_least))
    return jnp.where(unmasked, best, tokens), unmasked, logp


def _whole(config: LlamaConfig, lens):
    """The positions of a prompt's whole blocks: what is prefilled."""
    return lens // config.block_length * config.block_length


def diffusion_prefill_program(model: LlamaModel, config: LlamaConfig,
                              mesh=None):
    """The jitted prefill of a block-diffusion step."""
    n = config.block_length
    kernel = not _partitioned(mesh)

    def _diffusion_prefill(params, prompt_ids, prompt_lens, gen_blocks,
                           prefill_capacity=None):
        """``prompt_ids [B, S]`` -> ``(caches, stats)``: a cache a layer of
        ``S + gen_blocks * block_length`` positions holding the keys and
        values of each row's whole prompt blocks (``length`` = that many, a
        ``[B]`` vector), and the routed layers' reductions of
        ``score_labels_program`` (``expert_load_max`` / ``_mean``
        ``[layers]``, ``chosen [layers, B, S, k]``)."""
        B, S = prompt_ids.shape
        whole = _whole(config, prompt_lens.astype(jnp.int32))
        views = [BlockCausalPrefill(cache, whole, n, kernel)
                 for cache in init_caches(config, B, S + gen_blocks * n)]
        positions = jnp.arange(S)[None, :].repeat(B, 0)
        with jax.named_scope("diffusion.prefill"):
            (_, views), sown = model.apply(
                {"params": params}, prompt_ids, positions, None, views,
                prefill_lengths=whole if kernel else None,
                prefill_capacity=prefill_capacity, with_head=False,
                mutable=["intermediates"],
            )
        return ([view.cache for view in views],
                _routing_stats(sown, config))

    return profiled_jit(_diffusion_prefill, name="llama_diffusion_prefill",
                        static_argnames=("gen_blocks", "prefill_capacity"))


def diffusion_denoise_program(model: LlamaModel, config: LlamaConfig):
    """The jitted block loop of a block-diffusion step."""
    n, steps = config.block_length, config.denoising_steps
    at_least = n // steps
    mask_id = config.mask_token_id

    def one_pass(params, caches, tokens, positions, commit: bool):
        """The block's positions through the layers over the caches:
        ``(logits or None, caches, chosen [layers, B, n, k])``."""
        views = [BlockPass(cache, commit) for cache in caches]
        (logits, views), sown = model.apply(
            {"params": params}, tokens, positions, None, views,
            with_head=not commit, mutable=["intermediates"])
        chosen = _expert_ids(jnp.stack(_sown_by_layer(sown, "chosen")),
                             config.n_experts)
        return logits, [view.cache for view in views], chosen

    def _diffusion_denoise(params, caches, prompt_ids, prompt_lens,
                           gen_blocks):
        """``gen_blocks`` blocks a row from ``caches`` (the prefill's,
        donated).  Returns ``(out, caches)``; ``out`` holds, a block
        (leading axis ``gen_blocks``): ``tokens [G, B, n]`` (the clean
        block), ``fresh [G, B, n]`` (the position was generated, not the
        prompt's), ``unmask_pass [G, B, n]`` (the pass that unmasked it, -1
        for the prompt's), ``token_logp [G, B, n]`` (the token's
        log-probability at that pass), ``denoise_passes [G]``,
        ``positions_masked [G]`` (masked positions on entry, summed over
        the block's passes) and the experts every pass chose
        (``chosen_denoise [G, steps, layers, B, n, k]``, rows of passes
        that did not run are zero; ``chosen_commit [G, layers, B, n,
        k]``).  The caches come back with every block committed."""
        B, S = prompt_ids.shape
        lens = prompt_lens.astype(jnp.int32)
        whole = _whole(config, lens)
        offsets = jnp.arange(n, dtype=jnp.int32)[None, :]
        # the prompt's last, partial block opens the first generated one
        tail = jnp.take_along_axis(
            jnp.pad(prompt_ids, ((0, 0), (0, n))), whole[:, None] + offsets,
            axis=1)
        from_prompt = offsets < (lens - whole)[:, None]
        chosen_shape = (steps, config.n_layers, B, n, config.moe_top_k)

        def block(caches, g):
            positions = whole[:, None] + g * n + offsets
            clean = from_prompt & (g == 0)
            tokens = jnp.where(clean, tail, mask_id).astype(jnp.int32)

            def more(state):
                return jnp.any(state[1])

            def denoise(state):
                tokens, masked, passes, pass_of, logp_of, n_masked, rec = state
                with jax.named_scope("diffusion.denoise"):
                    logits, _, chosen = one_pass(params, caches, tokens,
                                                 positions, commit=False)
                with jax.named_scope("diffusion.unmask"):
                    tokens, unmasked, logp = unmask(
                        logits, tokens, masked, config.confidence_threshold,
                        at_least)
                return (tokens, masked & ~unmasked, passes + 1,
                        jnp.where(unmasked, passes, pass_of),
                        jnp.where(unmasked, logp, logp_of),
                        n_masked + masked.sum(dtype=jnp.int32),
                        jax.lax.dynamic_update_index_in_dim(
                            rec, chosen, passes, 0))

            zero = jnp.zeros((), jnp.int32)
            tokens, _, passes, pass_of, logp_of, n_masked, rec = (
                jax.lax.while_loop(more, denoise, (
                    tokens, ~clean, zero,
                    jnp.full((B, n), -1, jnp.int32),
                    jnp.zeros((B, n), jnp.float32), zero,
                    jnp.zeros(chosen_shape,
                              _expert_id_dtype(config.n_experts)))))
            with jax.named_scope("diffusion.commit"):
                _, caches, chosen_commit = one_pass(
                    params, caches, tokens, positions, commit=True)
            return caches, {
                "tokens": tokens, "fresh": ~clean, "unmask_pass": pass_of,
                "token_logp": logp_of, "denoise_passes": passes,
                "positions_masked": n_masked, "chosen_denoise": rec,
                "chosen_commit": chosen_commit}

        caches, out = jax.lax.scan(block, caches,
                                   jnp.arange(gen_blocks, dtype=jnp.int32))
        return out, caches

    return profiled_jit(_diffusion_denoise, name="llama_diffusion_denoise",
                        static_argnames=("gen_blocks",),
                        donate_argnames=("caches",))


def _block_causal_pairs(whole: np.ndarray, n: int) -> int:
    """(query, key) pairs of prefills of ``whole`` positions a row under
    the block-causal rule: a query sees its own block and those before."""
    blocks = whole // n
    return int((n * n * blocks * (blocks + 1) // 2).sum())


class BlockDiffusionClassifier(LlamaZeroShotClassifier):
    """Zero-shot sentiment the reference's way (generate a reply, read its
    first word: ``scripts/sentiment_classifier.py:85-108``) over a decoder
    that generates by diffusion over blocks.  A masked-diffusion model has
    no teacher-forced label likelihood to score (its likelihood of a
    multi-token label is a bound over maskings), so ``launch`` runs the two
    programs of the module's head in place of the scoring program, and
    ``collect`` reads the label from the generated tokens: the first that
    is a label word's id, else ``Neutral``, as ``normalise_label`` falls
    back; with a tokenizer directory the text is decoded and normalised."""

    name = "llama-block-diffusion"

    def __init__(self, config: LlamaConfig, **kwargs) -> None:
        super().__init__(config=config, **kwargs)
        self._prefill = diffusion_prefill_program(
            self.model, self.config, self.mesh)
        self._denoise = diffusion_denoise_program(self.model, self.config)
        self.gen_blocks = -(-MAX_NEW_TOKENS // config.block_length)
        # label word id -> label, for tokenizers that give a label one token
        self._label_of = {int(row[0]): label for row, label in zip(
            self._label_ids, SUPPORTED_LABELS)}

    @property
    def decode_runtime_refusal(self) -> Optional[str]:
        return BLOCK_STEP_REFUSAL

    def transfer(self, prepared):
        from music_analyst_tpu.runtime.wire import count_h2d_bytes

        texts, prompt_ids, prompt_lens = prepared
        count_h2d_bytes([prompt_ids, prompt_lens])
        lens = prompt_lens.astype(np.int64)
        whole = _whole(self.config, lens)
        # prompt tokens, the prefilled ones with their block-causal pairs,
        # and the token slots the prefill's expert layers run for them
        real = (int(lens.sum()), int(whole.sum()),
                _block_causal_pairs(whole, self.config.block_length),
                _prefill_capacity(self.config, self.mesh, whole,
                                  prompt_ids.shape))
        return (texts, jnp.asarray(prompt_ids), jnp.asarray(prompt_lens),
                real)

    def launch(self, transferred, keep_caches: bool = False):
        """Dispatch the prefill and the block loop back to back (async
        dispatch: the handle holds device arrays, nothing blocks).  The
        committed caches ride in the handle only where a caller asks
        (``keep_caches``: a comparison with a reference); else they are
        dropped here and freed when the loop is done with them."""
        texts, prompt_ids, prompt_lens, real = transferred
        caches, stats = self._prefill(
            self.params, prompt_ids, prompt_lens, gen_blocks=self.gen_blocks,
            prefill_capacity=real[3])
        out, caches = self._denoise(
            self.params, caches, prompt_ids, prompt_lens,
            gen_blocks=self.gen_blocks)
        if keep_caches:
            stats = dict(stats, caches=caches)
        return texts, out, stats, prompt_ids.shape, real

    def generated(self, out) -> List[List[int]]:
        """A row's new tokens in order, the prompt's own left out."""
        tokens = np.asarray(out["tokens"]).transpose(1, 0, 2)   # [B, G, n]
        fresh = np.asarray(out["fresh"]).transpose(1, 0, 2)
        return [row[new].tolist() for row, new in zip(
            tokens.reshape(len(tokens), -1), fresh.reshape(len(fresh), -1))]

    def _label(self, ids: Sequence[int]) -> str:
        if getattr(self.tokenizer, "closes_labels", False):
            return next((self._label_of[i] for i in ids
                         if i in self._label_of), "Neutral")
        return normalise_label(self.tokenizer.decode(ids))

    def collect(self, handle) -> List[str]:
        texts, out, stats, (rows, width), real = handle
        generated = self.generated(out)
        self._count_diffusion_step(rows, width, real, out, stats)
        return ["Neutral" if not text.strip() else self._label(ids)
                for text, ids in zip(texts, generated)]

    def _count_diffusion_step(self, rows, width, real, out, stats) -> None:
        """What one step computed, into the run's telemetry: counters
        ``diffusion.*``, ``decoder.tokens_real`` / ``_computed`` (positions
        through the layers: the prefilled blocks' and each pass's; computed
        includes the prefill's padding, or its ``capacity`` slots' fillers
        where it ran on the compact token set), ``moe.*``, gauge
        ``kv_cache_bytes``, and the step's shape and real counts on the
        span the engine has open (``compute``)."""
        from music_analyst_tpu.telemetry import get_telemetry

        tel, cfg = get_telemetry(), self.config
        n, blocks = cfg.block_length, self.gen_blocks
        tokens_real, prefilled, pairs, capacity = real
        passes = np.asarray(out["denoise_passes"], np.int64)     # [G]
        denoise, commit = int(passes.sum()), blocks
        masked = int(np.asarray(out["positions_masked"], np.int64).sum())
        unmasked = int(np.asarray(out["fresh"]).sum())
        # a pass of block g reads prefilled + g * n cached keys a row, and
        # its own n
        pass_pairs = int(sum(
            (int(p) + 1) * n * (prefilled + rows * (g + 1) * n)
            for g, p in enumerate(passes)))
        pass_positions = rows * n * (denoise + commit)
        slots = rows * width if capacity is None else capacity
        tel.count("diffusion.blocks", blocks)
        tel.count("diffusion.denoise_passes", denoise)
        tel.count("diffusion.commit_passes", commit)
        tel.count("diffusion.tokens_unmasked", unmasked)
        tel.count("decoder.tokens_real", prefilled + pass_positions)
        tel.count("decoder.tokens_computed",
                  stream_slots(cfg, (rows, width), capacity)
                  + pass_positions)
        load = self._count_expert_load(
            stats, slots,
            pass_positions * cfg.moe_top_k * len(stats["expert_load_max"]))
        tel.gauge("kv_cache_bytes", int(
            rows * (width + blocks * n) * cfg.n_layers * 2
            * cfg.n_kv_heads * cfg.attn_head_dim * 2))
        tel.current_span().set(
            rows=rows, width=width, tokens_real=tokens_real,
            tokens_prefilled=prefilled, token_pairs=pairs,
            pass_pairs=pass_pairs, gen_blocks=blocks, block_length=n,
            denoise_passes=denoise, commit_passes=commit,
            positions_masked=masked, tokens_unmasked=unmasked, **load)

    # The reference's own path, ``generate`` and its batch forms: the
    # autoregressive programs do not apply, the block loop does.

    def generate_batch(self, prompts: Sequence[str],
                       max_new_tokens: int = MAX_NEW_TOKENS,
                       early_exit: bool = True) -> List[str]:
        """``max_new_tokens`` is met in whole blocks (the reference's 16 is
        four blocks of four); ``early_exit`` has nothing to cut, a block's
        passes already stop when no mask is left."""
        if -(-max_new_tokens // self.config.block_length) != self.gen_blocks:
            raise ValueError(
                f"this model generates {self.gen_blocks} blocks of "
                f"{self.config.block_length} tokens a call")
        ids, lens = self.tokenizer.encode_batch(prompts, self.max_prompt_len)
        ids, lens = self._trim_prompt_pad(ids, lens)
        from music_analyst_tpu.runtime.wire import narrow_lengths

        handle = self.launch(self.transfer(
            (prompts, ids, narrow_lengths(lens, self.max_prompt_len))))
        eos = self.tokenizer.eos_id
        outs = []
        for row in self.generated(handle[1]):
            if eos in row:
                row = row[:row.index(eos)]
            outs.append(self.tokenizer.decode(row))
        return outs

    def generate(self, prompt: str,
                 max_new_tokens: int = MAX_NEW_TOKENS) -> str:
        return self.generate_batch([prompt], max_new_tokens)[0]

    def classify_batch_by_generation(self, texts: Sequence[str]) -> List[str]:
        return self.classify_batch(texts)

    def classify_by_generation(self, text: str) -> str:
        return self.classify_batch([text])[0]
