"""The prefill attention kernel of the latent-attention block
(``ops/mla_prefill_attention.py``) under the Pallas interpreter: against
``models/mla.blocked_attention`` with the causal-and-padding mask on every
real position, the choice ``MLAttention`` makes from the shapes, and the
scoring program of ``kanana-tiny`` end to end at a width the kernel takes.

Whether Mosaic lowers it at the published widths is
``tests/test_mosaic_aot.py``'s question; times and the MXU's rounding are
the chip's (``PERF.md``).
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(REPO, "perfbench") not in sys.path:
    sys.path.insert(0, os.path.join(REPO, "perfbench"))

from reference import deepseek_v3_f32 as ref  # noqa: E402

from music_analyst_tpu.models import llama, mla  # noqa: E402
from music_analyst_tpu.models.layers import causal_mask, padding_mask  # noqa: E402
from music_analyst_tpu.models.llama import (  # noqa: E402
    PRESETS,
    LlamaZeroShotClassifier,
)
from music_analyst_tpu.models.mla import (  # noqa: E402
    LatentCache,
    MLAttention,
    blocked_attention,
)
from music_analyst_tpu.models.moe import RealPositions  # noqa: E402
from music_analyst_tpu.ops.mla_prefill_attention import (  # noqa: E402
    BLOCK,
    mla_prefill_attention,
    mla_prefill_attention_packed,
    packed_prefill_block,
    prefill_block,
)
from music_analyst_tpu.profiling.compile import profiled_jit  # noqa: E402

SEQ = 3 * BLOCK          # three query blocks: first, middle, last
PAD = 8                  # the cache's buffer is longer than the prompt

# (heads, nope, rope, v): the published 192 | 128 and kanana-tiny's 24 | 16
WIDTHS = {"192|128": (2, 128, 64, 128), "24|16": (4, 16, 8, 16)}

LENGTHS = {
    "inside_first_block": [5, 100, BLOCK - 1, 17],
    "end_inside_a_block": [BLOCK + 44, 2 * BLOCK + 1, SEQ - 1, BLOCK + 1],
    "whole_rows": [SEQ] * 4,
    "differ_by_row": [1, BLOCK, SEQ, 2 * BLOCK],
}


def _operands(widths, dtype, seed=0):
    heads, nope, rope, v_dim = WIDTHS[widths]
    keys = jax.random.split(jax.random.key(seed), 4)
    rows = 4
    return (
        jax.random.normal(keys[0], (rows, SEQ, heads, nope), dtype),
        jax.random.normal(keys[1], (rows, SEQ, heads, rope), dtype),
        jax.random.normal(keys[2], (rows, SEQ + PAD, heads, nope + v_dim),
                          dtype),
        jax.random.normal(keys[3], (rows, SEQ + PAD, rope), dtype),
    )


def _both(q_nope, q_rope, kv, k_rope, lengths):
    """The kernel's output and the blocked form's under the mask the
    scoring program builds (``models/llama.py::_score_labels``)."""
    rows, seq, heads, nope = q_nope.shape
    scale = (nope + q_rope.shape[-1]) ** -0.5
    lengths = jnp.asarray(lengths, jnp.int32)
    mask = causal_mask(seq, seq + PAD, 0) & jnp.pad(
        padding_mask(lengths, seq), ((0, 0), (0, 0), (0, 0), (0, PAD)))
    want = blocked_attention(q_nope, q_rope, kv[..., :nope], k_rope,
                             kv[..., nope:], mask, scale, 128)
    got = mla_prefill_attention(
        q_nope.reshape(rows, seq, -1), q_rope.reshape(rows, seq, -1),
        kv.reshape(rows, seq + PAD, -1), k_rope, lengths, heads, scale)
    return (np.asarray(got, np.float32).reshape(want.shape),
            np.asarray(want, np.float32))


@pytest.mark.parametrize("lengths", list(LENGTHS), ids=list(LENGTHS))
@pytest.mark.parametrize("widths", list(WIDTHS), ids=list(WIDTHS))
def test_kernel_equals_blocked_attention_on_real_positions(widths, lengths):
    """bfloat16 as served: the online softmax rounds its probabilities
    before the division, the row softmax after, so the two agree to a
    bfloat16 step of an output of size one; padding positions are finite,
    and zeros where a whole query block is padding."""
    got, want = _both(*_operands(widths, jnp.bfloat16), LENGTHS[lengths])
    assert np.isfinite(got).all()
    for row, n in enumerate(LENGTHS[lengths]):
        np.testing.assert_allclose(got[row, :n], want[row, :n],
                                   rtol=0, atol=0.02)
        dead = -(-n // BLOCK) * BLOCK        # first block with no real token
        assert not got[row, dead:].any()


@pytest.mark.parametrize("widths", list(WIDTHS), ids=list(WIDTHS))
def test_kernel_holds_the_equations_in_float32(widths):
    """float32 operands: nothing but the order of the sums differs."""
    lengths = LENGTHS["end_inside_a_block"]
    got, want = _both(*_operands(widths, jnp.float32, seed=1), lengths)
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(got[row, :n], want[row, :n],
                                   rtol=0, atol=2e-5)


def test_shapes_outside_the_regime_are_refused_not_served():
    assert prefill_block(2 * BLOCK) == BLOCK and prefill_block(1024) == BLOCK
    assert [prefill_block(n) for n in (8, BLOCK, BLOCK + 128, 3 * BLOCK - 8)
            ] == [0, 0, 0, 0]
    q_nope, q_rope, kv, k_rope = _operands("24|16", jnp.float32)
    one_block = (q_nope[:, :BLOCK].reshape(4, BLOCK, -1),
                 q_rope[:, :BLOCK].reshape(4, BLOCK, -1),
                 kv.reshape(4, SEQ + PAD, -1), k_rope)
    with pytest.raises(ValueError, match="prefill_block"):
        mla_prefill_attention(*one_block, jnp.full((4,), 9), 4, 0.2)
    short_keys = (q_nope.reshape(4, SEQ, -1), q_rope.reshape(4, SEQ, -1),
                  kv[:, :SEQ - 8].reshape(4, SEQ - 8, -1), k_rope[:, :SEQ - 8])
    with pytest.raises(ValueError, match="do not cover"):
        mla_prefill_attention(*short_keys, jnp.full((4,), 9), 4, 0.2)


# --------------------------------------------------------- the packed form

# rows laid one behind the other in a token set of ``capacity`` slots
# (lengths, capacity): a row of one token, exactly a block, a block + 1
# and the full width, fillers behind them sharing the last row's block;
# rows that fill the capacity to the last slot; two whole blocks of
# fillers behind the last row; rows with no token at all among the others
PACKED = {
    "one_block_block+1_full": ([1, BLOCK, BLOCK + 1, SEQ], 6 * BLOCK),
    "fills_the_capacity": ([SEQ, BLOCK - 1, 1, 2 * BLOCK], 6 * BLOCK),
    "dead_blocks_behind": ([5, 100, BLOCK - 1, 17], 4 * BLOCK),
    "empty_rows": ([0, BLOCK + 44, 0, 7], 2 * BLOCK),
}


def _packed_and_padded(widths, dtype, lengths, capacity, seed=0):
    """The packed kernel's output put back at ``[rows, SEQ]`` and the
    padded kernel's on the same operands, with the packed form's own
    ``[capacity, H*v]`` result."""
    heads, nope, rope, _ = WIDTHS[widths]
    q_nope, q_rope, kv, k_rope = _operands(widths, dtype, seed)
    rows = q_nope.shape[0]
    flat = (q_nope.reshape(rows, SEQ, -1), q_rope.reshape(rows, SEQ, -1),
            kv[:, :SEQ].reshape(rows, SEQ, -1), k_rope[:, :SEQ])
    scale = (nope + rope) ** -0.5
    lens = jnp.asarray(lengths, jnp.int32)
    want = mla_prefill_attention(*flat, lens, heads, scale)
    compact = RealPositions.of(lens, SEQ, capacity)
    got = mla_prefill_attention_packed(
        *(compact.gather(a) for a in flat), lens, SEQ, heads, scale)
    assert got.shape == (capacity, want.shape[-1])
    return (np.asarray(compact.put_back(got), np.float32),
            np.asarray(want, np.float32), np.asarray(got, np.float32))


@pytest.mark.parametrize("case", list(PACKED), ids=list(PACKED))
@pytest.mark.parametrize("widths", list(WIDTHS), ids=list(WIDTHS))
def test_packed_kernel_equals_the_padded_one_on_real_positions(widths, case):
    """bfloat16 as served: every real slot reads its own row's slots up to
    itself and nothing of its neighbours', wherever in a block its row
    starts (a bfloat16 step apart where the tiles are summed in another
    order); fillers are finite, and zeros in the blocks behind the last
    real slot.  That every real slot is right also says no query block
    wrote over another's slots."""
    lengths, capacity = PACKED[case]
    back, want, got = _packed_and_padded(widths, jnp.bfloat16, lengths,
                                         capacity)
    assert np.isfinite(got).all()
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(back[row, :n], want[row, :n],
                                   rtol=0, atol=0.02)
        assert not back[row, n:].any()        # put_back's zeros
    dead = -(-sum(lengths) // BLOCK) * BLOCK  # first block with no real slot
    assert not got[dead:].any()
    if sum(lengths) == capacity:
        assert dead == capacity               # no filler at all


@pytest.mark.parametrize("widths", list(WIDTHS), ids=list(WIDTHS))
def test_packed_kernel_holds_the_equations_in_float32(widths):
    """float32 operands: nothing but the order of the sums differs, with
    rows that start inside a block and end inside another."""
    lengths, capacity = PACKED["one_block_block+1_full"]
    back, want, _ = _packed_and_padded(widths, jnp.float32, lengths,
                                       capacity, seed=1)
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(back[row, :n], want[row, :n],
                                   rtol=0, atol=2e-5)


def test_packed_shapes_outside_the_regime_are_refused_not_served():
    assert packed_prefill_block(1024, 12288) == BLOCK
    assert packed_prefill_block(2 * BLOCK, 3 * BLOCK) == BLOCK
    # rows of a width the padded kernel refuses; slots that are not blocks
    assert [packed_prefill_block(*shape) for shape in (
        (BLOCK, 4 * BLOCK), (2 * BLOCK + 128, 4 * BLOCK),
        (2 * BLOCK, 3 * BLOCK + 64), (64, 128))] == [0, 0, 0, 0]
    q_nope, q_rope, kv, k_rope = _operands("24|16", jnp.float32)
    flat = (q_nope.reshape(4 * SEQ, -1), q_rope.reshape(4 * SEQ, -1),
            kv[:, :SEQ].reshape(4 * SEQ, -1), k_rope[:, :SEQ].reshape(
                4 * SEQ, -1))
    lens = jnp.full((4,), 9)
    with pytest.raises(ValueError, match="packed_prefill_block"):
        mla_prefill_attention_packed(
            *(a[:3 * BLOCK + 64] for a in flat), lens, SEQ, 4, 0.2)
    with pytest.raises(ValueError, match="not the queries'"):
        mla_prefill_attention_packed(
            flat[0], flat[1], flat[2][:BLOCK], flat[3][:BLOCK], lens, SEQ,
            4, 0.2)


# ------------------------------------------------------------ the choice

def _tiny_attention():
    cfg = PRESETS["kanana-tiny"]()
    return cfg, MLAttention(
        n_heads=cfg.n_heads, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, rope_theta=cfg.rope_theta,
        rope_interleave=cfg.rope_interleave, max_positions=cfg.max_seq_len,
        norm_eps=cfg.rms_norm_eps, dtype=jnp.float32)


def test_packed_attention_layer_equals_the_padded_layer_on_real_positions():
    """``MLAttention`` handed the compact token set (``packed``): its
    output put back equals the padded call's on every real position, the
    cache holds the real positions' ``latents`` / ``rope_keys`` and zeros
    behind them, and the compile record names the packed kernel."""
    cfg, attention = _tiny_attention()
    n_queries, rows = 2 * BLOCK, 4
    x = jax.random.normal(jax.random.key(5), (rows, n_queries, cfg.dim),
                          jnp.float32)
    params = attention.init(jax.random.key(3), x[:, :8])
    lens = jnp.asarray([n_queries, 1, BLOCK + 1, 100], jnp.int32)
    capacity = 4 * BLOCK
    buffer = n_queries + PAD
    positions = jnp.broadcast_to(jnp.arange(n_queries), (rows, n_queries))

    def forward(params, x, lens, packed):
        cache = LatentCache.zeros(rows, buffer, cfg.kv_lora_rank,
                                  cfg.qk_rope_head_dim, jnp.float32)
        if not packed:
            return attention.apply(params, x, None, positions, cache,
                                   prefill_lengths=lens)
        compact = RealPositions.of(lens, n_queries, capacity)
        out, cache = attention.apply(
            params, compact.gather(x)[None], None,
            compact.gather(positions)[None], cache, prefill_lengths=lens,
            packed=compact)
        return compact.put_back(out[0]), cache

    program = profiled_jit(forward, name="mla_packed", static_argnums=(3,))
    got, got_cache = program(params, x, lens, True)
    (record,) = program.records.values()
    assert record.attention_paths == {"mla_flash_packed": 1}
    assert record.traced_paths == {"mla.expanded": 1, "mla.compact": 1}
    want, want_cache = program(params, x, lens, False)
    real = np.arange(n_queries)[None, :] < np.asarray(lens)[:, None]
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real],
                               rtol=0, atol=2e-5)
    for name in ("latents", "rope_keys"):
        mine = np.asarray(getattr(got_cache, name))[:, :n_queries]
        np.testing.assert_allclose(
            mine[real],
            np.asarray(getattr(want_cache, name))[:, :n_queries][real],
            rtol=0, atol=2e-5)
        assert not mine[~real].any()
    assert int(got_cache.length) == n_queries


@pytest.mark.parametrize("n_queries,lengths,cache,paths,traced", [
    (2 * BLOCK, True, False, {"mla_flash": 1}, {"mla.expanded": 1}),
    (2 * BLOCK, True, True, {"mla_flash": 1}, {"mla.expanded": 1}),
    (2 * BLOCK, False, True, {"mla_blocked": 1}, {"mla.expanded": 1}),
    (BLOCK, True, True, {"mla_blocked": 1}, {"mla.expanded": 1}),
    (2 * BLOCK + 128, True, False, {"mla_blocked": 1}, {"mla.expanded": 1}),
    (64, True, True, {}, {"mla.absorbed": 1}),
], ids=["lengths", "lengths+cache", "no_lengths", "one_block",
        "not_whole_blocks", "absorbed"])
def test_the_shapes_choose_the_path_and_the_compile_record_names_it(
        n_queries, lengths, cache, paths, traced):
    """``attention_paths`` / ``traced_paths`` of the compiled shape, as a
    run's manifest carries them; and whichever path ran, the real
    positions read what the mask alone gives."""
    cfg, attention = _tiny_attention()
    x = jax.random.normal(jax.random.key(2), (2, n_queries, cfg.dim),
                          jnp.float32)
    params = attention.init(jax.random.key(3), x[:, :8])
    lens = jnp.asarray([n_queries - 3, n_queries // 2 + 1], jnp.int32)
    buffer = n_queries + PAD if cache else n_queries
    mask = causal_mask(n_queries, buffer, 0) & jnp.pad(
        padding_mask(lens, n_queries),
        ((0, 0), (0, 0), (0, 0), (0, buffer - n_queries)))

    def forward(params, x, lens, with_lengths):
        latent = LatentCache.zeros(
            2, buffer, cfg.kv_lora_rank, cfg.qk_rope_head_dim,
            jnp.float32) if cache else None
        out = attention.apply(
            params, x, mask, None, latent,
            prefill_lengths=lens if with_lengths else None)
        return out[0] if cache else out

    program = profiled_jit(forward, name="mla_choice",
                           static_argnums=(3,))
    got = program(params, x, lens, lengths)
    (record,) = program.records.values()
    assert record.attention_paths == paths
    assert record.traced_paths == traced
    want = forward(params, x, lens, False)      # the mask alone
    for row, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(got[row, :n], want[row, :n],
                                   rtol=0, atol=2e-5)


# ---------------------------------------------------- the scoring program

_WORDS = ("love rain night baby tears dance road fire cold heart sun blue "
          "you me the and never always gone stay").split()


def _hf(name):
    path = os.path.join(REPO, "music_analyst_tpu", "models", "presets",
                        name + ".json")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def _scores(clf, ids, lens):
    scores, stats = clf._score_labels(
        clf.params, jnp.asarray(ids), jnp.asarray(lens),
        jnp.asarray(clf._label_ids), jnp.asarray(clf._label_lens))
    (record,) = clf._score_labels.records.values()
    return (np.asarray(scores, np.float64), np.asarray(stats["chosen"]),
            ref.prefer_from_system(stats["chosen"], stats["chosen_labels"],
                                   lens), record)


def test_scoring_program_through_the_kernel_agrees_with_the_blocked_path(
        monkeypatch):
    """``kanana-tiny`` as served (bfloat16) at a 512-wide step: one
    classifier compiles the prefill through the kernel, a second (same
    seed, ``prefill_block`` answering 0) through the blocked form.  Label
    scores agree within the model tests' tolerance, the experts of real
    positions are equal but for a few ties, and every choice of the
    kernel's run is the float32 reference's own or a tie inside its
    margin."""
    rng = np.random.default_rng(7)
    lyrics = [" ".join(rng.choice(_WORDS, size=int(n)))
              for n in (30, 140, 260, 330, 395)] + [""]
    served = LlamaZeroShotClassifier(config=PRESETS["kanana-tiny"](), seed=0)
    ids, lens = (np.asarray(a) for a in served._encode_prompts(lyrics))
    assert ids.shape[1] == 2 * BLOCK and lens.min() < BLOCK < lens.max()
    got, chosen, prefer, record = _scores(served, ids, lens)
    assert record.attention_paths == {"mla_flash": 3}
    assert record.traced_paths["mla.expanded"] == 3
    # one device: the lengths are handed on to the latent layers
    assert llama._prefill_lengths(served.mesh, lens) is lens

    monkeypatch.setattr(mla, "prefill_block", lambda n_queries: 0)
    fallback = LlamaZeroShotClassifier(config=PRESETS["kanana-tiny"](), seed=0)
    want, chosen_blocked, _, record = _scores(fallback, ids, lens)
    assert record.attention_paths == {"mla_blocked": 3}

    tol = ref.TEST_TOLERANCE
    diff = np.abs(got - want)
    assert np.median(diff) < tol["label_score_median"], diff
    assert diff.max() < tol["label_score_max"], diff
    real = np.arange(ids.shape[1])[None, :] < lens[:, None]     # [B, S]
    same = (np.sort(chosen, -1) == np.sort(chosen_blocked, -1)).all(-1)
    assert same[:, real].mean() > 0.97          # [layers, B, S] -> ties apart
    judged = ref.label_scores(
        served.params, _hf("kanana-tiny"), ids, lens, served._label_ids,
        served._label_lens, prefer=prefer, margin=tol["route_margin"])
    assert judged["routing"]["wrong"] == tol["wrong_choices"]
    against_reference = np.abs(got - judged["scores"])
    assert np.median(against_reference) < tol["label_score_median"]
    assert against_reference.max() < tol["label_score_max"]
