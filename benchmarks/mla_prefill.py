"""The latent-attention prefill kernel against the blocked XLA form, at the
decoder cell's step.

Backs the per-kernel times of ``PERF.md`` (sections 5 and 6): 32 rows x
1,024 queries x 32 heads of 192 | 128, bfloat16, with the prompt lengths
of the benchmark's own corpus (``perfbench/corpus.py`` under
``perfbench/configs/kanana-2-30b-a3b.json``'s generator parameters, four
batches of 32).  ``blocked`` is ``models/mla.blocked_attention`` under the
causal-and-padding mask over the cache's 1,032-key buffer; ``packed`` is
``ops/mla_prefill_attention.py`` on the same rows laid one behind the other
in ``models/compact_stream.compact_capacity`` slots (what the compact
prefill calls); ``packed_full_rows`` the same kernel with every row full,
at the step's own 32,768 slots (what a step of full rows calls).  Errors
are against the blocked form on the real positions.  ``executed_share`` is
the part of the ``S x S`` squares the kernel's tiles cover, ``tflops``
counts those tiles' multiply-adds.

    chiprun -- python3 bench.py --suite=mla_prefill
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

from benchmarks import suite
from benchmarks._util import device_info, smoke, timed

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _prompt_lengths(width: int, seed: int = 1) -> np.ndarray:
    """Prompt lengths of one job of ``llm_sentiment_releases``."""
    sys.path.insert(0, os.path.join(_REPO, "perfbench"))
    try:
        import corpus
    finally:
        sys.path.pop(0)
    from music_analyst_tpu.models.llama import (
        LYRICS_TRUNCATION,
        PROMPT_TEMPLATE,
    )
    from music_analyst_tpu.models.tokenization import HashWordLMTokenizer

    path = os.path.join(_REPO, "perfbench", "configs",
                        "kanana-2-30b-a3b.json")
    with open(path, encoding="utf-8") as fh:
        params = json.load(fh)["corpus"]["generator"]
    prompts = [
        PROMPT_TEMPLATE.format(lyrics=row[3].strip()[:LYRICS_TRUNCATION])
        for row in corpus.generate_rows(params, seed)
    ]
    _, lens = HashWordLMTokenizer(128256).encode_batch(prompts, width)
    return np.asarray(lens)


@suite("mla_prefill")
def run() -> dict:
    import jax
    import jax.numpy as jnp

    from music_analyst_tpu.models.compact_stream import (
        RealPositions,
        compact_capacity,
    )
    from music_analyst_tpu.models.layers import causal_mask, padding_mask
    from music_analyst_tpu.models.mla import blocked_attention
    from music_analyst_tpu.ops.flash_attention import packed_visited_pairs
    from music_analyst_tpu.ops.mla_prefill_attention import (
        mla_prefill_attention_packed,
    )
    from music_analyst_tpu.ops.packed_stream import stream_tile

    if smoke():
        rows, seq, heads, nope, rope, v_dim, n_batches = (
            4, 512, 4, 16, 8, 16, 1)
        lens_all = np.asarray([300, 41, 256, 1])
    else:
        rows, seq, heads, nope, rope, v_dim, n_batches = (
            32, 1024, 32, 128, 64, 128, 4)
        lens_all = _prompt_lengths(seq)
    keys = seq + 8                       # the cache's buffer: 8 label slots
    scale = (nope + rope) ** -0.5
    ks = jax.random.split(jax.random.key(0), 4)
    q_nope = jax.random.normal(ks[0], (rows, seq, heads, nope), jnp.bfloat16)
    q_rope = jax.random.normal(ks[1], (rows, seq, heads, rope), jnp.bfloat16)
    kv = jax.random.normal(ks[2], (rows, keys, heads, nope + v_dim),
                           jnp.bfloat16)
    k_rope = jax.random.normal(ks[3], (rows, keys, rope), jnp.bfloat16)
    batches = [jnp.asarray(lens_all[i * rows:(i + 1) * rows], jnp.int32)
               for i in range(n_batches)]
    full = jnp.full((rows,), seq, jnp.int32)

    @jax.jit
    def blocked(lens):
        mask = causal_mask(seq, keys, 0) & jnp.pad(
            padding_mask(lens, seq), ((0, 0),) * 3 + ((0, keys - seq),))
        return blocked_attention(q_nope, q_rope, kv[..., :nope], k_rope,
                                 kv[..., nope:], mask, scale, 128)

    flat = (q_nope.reshape(rows, seq, -1), q_rope.reshape(rows, seq, -1),
            kv[:, :seq].reshape(rows, seq, -1), k_rope[:, :seq])

    # the packed form's operands: the rows' real positions, gathered
    # outside the timed call (in the model the projections write them so)
    capacity = max(compact_capacity(int(np.asarray(b).sum()), rows, seq)
                   for b in batches)
    compact = [RealPositions.of(b, seq, capacity) for b in batches]
    gathered = [tuple(c.gather(a) for a in flat) for c in compact]
    whole = RealPositions.of(full, seq, rows * seq)
    gathered_full = tuple(whole.gather(a) for a in flat)

    @jax.jit
    def packed(operands, lens):
        return mla_prefill_attention_packed(*operands, lens, seq, heads,
                                            scale)

    def ms(fn, lens_list, operands=None):
        """Mean milliseconds a call over ``lens_list`` (``fn(lens)``, or
        ``fn(operands[i], lens)``), one readback."""
        calls = ([(lens,) for lens in lens_list] if operands is None
                 else list(zip(operands, lens_list)))

        def go():
            return [fn(*args) for args in calls][-1].reshape(-1)[:8]
        go()
        return timed(go)[0] / len(lens_list) * 1e3

    def error(stream, operands, lens):
        """Largest distance from the blocked form on a real position."""
        want = np.asarray(blocked(lens), np.float32)
        got = np.asarray(stream.put_back(packed(operands, lens)),
                         np.float32).reshape(want.shape)
        return max(float(np.abs(got[b, :n] - want[b, :n]).max())
                   for b, n in enumerate(np.asarray(lens)))

    block = stream_tile(seq, capacity)
    flops_a_pair = heads * 2 * (nope + rope + v_dim)
    pairs = float(np.mean([packed_visited_pairs(b, capacity, seq, block)
                           for b in batches]))
    pairs_full = packed_visited_pairs(full, rows * seq, seq, block)
    packed_ms = ms(packed, batches, gathered)
    full_ms = ms(packed, [full], [gathered_full])
    return {
        "suite": "mla_prefill",
        "smoke": smoke(),
        "device": device_info(),
        "shape": {"rows": rows, "queries": seq, "keys": keys, "heads": heads,
                  "widths": f"{nope + rope}|{v_dim}", "block": block},
        "prompt_len_median": float(np.median(lens_all)),
        "blocked_ms": round(ms(blocked, batches), 3),
        "packed_ms": round(packed_ms, 3),
        "packed_capacity": capacity,
        "packed_full_rows_ms": round(full_ms, 3),
        "packed_full_rows_capacity": rows * seq,
        "executed_share": round(pairs / (rows * seq * seq), 4),
        "packed_tflops": round(pairs * flops_a_pair / packed_ms / 1e9, 2),
        "packed_full_rows_tflops": round(
            pairs_full * flops_a_pair / full_ms / 1e9, 2),
        "max_abs_error_real_positions": error(compact[0], gathered[0],
                                              batches[0]),
        "packed_full_rows_max_abs_error": error(whole, gathered_full, full),
    }
