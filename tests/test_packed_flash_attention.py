"""The packed flash kernel (``ops/flash_attention.flash_attention_packed``):
the grouped-query prefill's attention on the compact token stream, against
the cache view's masked XLA form (``ops/kv_cache.BlockCausalPrefill`` with
``kernel=False``) on the same operands put back at ``[B, S]``, on the real
positions; the tiles it runs against a brute-force count of the tiles a
mask needs; and the compile records of the presets whose compact prefill
takes it.  Interpreted on the CPU (``interpret_default``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from music_analyst_tpu.models.compact_stream import (
    RealPositions,
    compact_capacity,
)
from music_analyst_tpu.ops import flash_attention
from music_analyst_tpu.ops.flash_attention import (
    flash_attention_packed,
    packed_kv_heads,
    packed_visited_pairs,
)
from music_analyst_tpu.ops.kv_cache import (
    BlockCausalPrefill,
    KVCache,
    prefill_tile_pairs,
)
from music_analyst_tpu.ops.packed_stream import PACKED_TILE, stream_tile

WIDTH, DIM, BLOCK = 32, 16, 8

# (lengths, capacity, query heads, key/value heads, block-causal n, window,
# scale): rows that straddle blocks, an empty row, fillers sharing the last
# real slot's block and whole blocks of them behind it; rows shorter and
# longer than the window; groups of 9, 6 and 4 query heads a key/value head
CASES = {
    "causal-9:1": ([32, 5, 0, 16], 72, 9, 1, 1, 0, None),
    "window-6:1": ([32, 5, 0, 16], 72, 6, 1, 1, 8, None),
    "window-4:1-scale": ([32, 5, 0, 19], 64, 8, 2, 1, 8, 0.3),
    "block-causal-4:1": ([32, 8, 0, 20], 72, 8, 2, 4, 0, None),
    "block-causal-window": ([4, 28, 0, 24], 64, 6, 1, 4, 8, 0.25),
}


def _both(case, dtype, seed=0):
    """The packed kernel's ``[C, H*D]`` result, the same put back at ``[B,
    S, H, D]``, and the view's masked form on the put-back operands."""
    lengths, capacity, heads, kv_heads, n, window, scale = case
    rows = len(lengths)
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (rows, WIDTH, heads, DIM)).astype(dtype)
    k = jax.random.normal(ks[1], (rows, WIDTH, kv_heads, DIM)).astype(dtype)
    v = jax.random.normal(ks[2], (rows, WIDTH, kv_heads, DIM)).astype(dtype)
    lens = jnp.asarray(lengths, jnp.int32)
    stream = RealPositions.of(lens, WIDTH, capacity)
    on_stream = [stream.gather(a) for a in (q, k, v)]
    got = flash_attention_packed(
        *(a.reshape(capacity, -1) for a in on_stream), lens, WIDTH, heads,
        BLOCK, block_causal=n, scale=scale, window=window)
    view = BlockCausalPrefill(
        KVCache.zeros(rows, WIDTH, kv_heads, DIM, dtype), lens, n,
        kernel=False, scale=scale, window=window,
    ).update(*(stream.put_back(a) for a in on_stream[1:]))
    want = view.attend(stream.put_back(on_stream[0]))
    back = stream.put_back(got.reshape(capacity, heads, DIM))
    return (np.asarray(got, np.float32), np.asarray(back, np.float32),
            np.asarray(want, np.float32))


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_packed_kernel_is_the_masked_form_on_real_positions(case):
    """bfloat16 as served: every real slot reads its own row's slots under
    the rule (causal, block-causal of 4, within the window) and nothing of
    its neighbours' or the fillers', wherever its row starts in a block (a
    bfloat16 step of an output of size one apart: both round the
    probabilities to bfloat16 before P.V, the sums run in another order);
    fillers are finite, and zeros in the blocks behind the last real
    slot."""
    lengths, capacity = CASES[case][:2]
    got, back, want = _both(CASES[case], jnp.bfloat16)
    assert np.isfinite(got).all()
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(back[row, :n], want[row, :n],
                                   rtol=0, atol=0.02)
    dead = -(-sum(lengths) // BLOCK) * BLOCK  # first block with no real slot
    assert dead < capacity and not got[dead:].any()


def test_key_value_groups_take_grid_steps_of_their_own(monkeypatch):
    """Where a step of every head would overflow the step's VMEM
    (:data:`flash_attention.PACKED_VMEM_BYTES`, here none) the grid takes
    one key/value group with its query heads at a time (16 query heads on
    2, groups of 8 x 16 lanes): the same result as the masked form."""
    monkeypatch.setattr(flash_attention, "PACKED_VMEM_BYTES", 0)
    case = ([32, 5, 0, 19], 64, 16, 2, 1, 8, None)
    assert packed_kv_heads(16, 2, DIM, BLOCK) == 1
    got, back, want = _both(case, jnp.bfloat16)
    assert np.isfinite(got).all()
    for row, n in enumerate(case[0]):
        np.testing.assert_allclose(back[row, :n], want[row, :n],
                                   rtol=0, atol=0.02)


@pytest.mark.parametrize("case", ["window-4:1-scale", "block-causal-window"])
def test_packed_kernel_holds_the_equations_in_float32(case):
    """float32 operands: nothing but the order of the sums differs."""
    lengths = CASES[case][0]
    _, back, want = _both(CASES[case], jnp.float32, seed=1)
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(back[row, :n], want[row, :n],
                                   rtol=0, atol=2e-5)


def _tiles_a_mask_needs(lengths, block, window=0, n=1) -> int:
    """Brute force: the ``(query block, key block)`` pairs of slots that
    hold at least one real query and a key it sees, rows laid one behind
    the other."""
    tiles, start = set(), 0
    for length in lengths:
        q = np.arange(length)[:, None]
        k = np.arange(length)[None, :]
        seen = k <= (q // n * n + n - 1 if n > 1 else q)
        if window:
            seen &= k > q - window
        qs, ks = np.nonzero(seen)
        tiles |= set(zip((start + qs) // block, (start + ks) // block))
        start += length
    return len(tiles)


def _long_lyrics(rows, seed, n=1):
    rng = np.random.default_rng(seed)
    lens = np.clip(rng.lognormal(np.log(700), 0.35, rows), 1, 1024)
    return (lens.astype(np.int64) // n * n).tolist()


# lengths, width, capacity, block, window, block-causal n
TILE_CASES = {
    "small-causal": ([32, 5, 0, 16], 32, 72, 8, 0, 1),
    "small-window": ([32, 5, 0, 19], 32, 64, 8, 8, 1),
    "small-block-causal": ([4, 28, 0, 24], 32, 64, 8, 0, 4),
    "window-cell-sliding": (_long_lyrics(16, 1), 1024, 12288, 256, 512, 1),
    "window-cell-full": (_long_lyrics(16, 2), 1024, 12288, 256, 0, 1),
    "window-cell-128": (_long_lyrics(16, 3), 1024, 12288, 128, 512, 1),
    "diffusion-cell": (_long_lyrics(14, 4, 4) + [0, 4], 1024, 12288, 256,
                       0, 4),
}


@pytest.mark.parametrize("case", list(TILE_CASES), ids=list(TILE_CASES))
def test_the_tiles_the_packed_kernel_runs_are_the_tiles_a_mask_needs(case):
    """``packed_visited_pairs`` (the kernel's own rule,
    ``packed_step_runs``, on its own tables) counts exactly the tiles in
    which some real query sees some key: nothing the mask needs is
    skipped, nothing wholly outside it runs."""
    lengths, width, capacity, block, window, n = TILE_CASES[case]
    assert sum(lengths) <= capacity
    visited = packed_visited_pairs(lengths, capacity, width, block, window)
    assert visited == _tiles_a_mask_needs(lengths, block, window,
                                          n) * block * block


def test_prefill_tile_pairs_counts_the_kernel_that_ran():
    """The host count behind ``token_pairs_tiles``: the packed kernel's
    tiles where the prefill ran on the compact stream, the ``[B, S]``
    kernel's where it did not."""
    lengths = _long_lyrics(16, 5)
    capacity = compact_capacity(sum(lengths), 16, 1024)
    assert stream_tile(1024, capacity) == PACKED_TILE
    for window in (0, 512):
        assert prefill_tile_pairs(lengths, 1024, window, capacity) == (
            packed_visited_pairs(lengths, capacity, 1024, PACKED_TILE,
                                 window))
        assert prefill_tile_pairs(lengths, 1024, window) > (
            prefill_tile_pairs(lengths, 1024, window, capacity))


def test_shapes_outside_the_regime_are_refused_not_served():
    assert stream_tile(1024, 12288) == PACKED_TILE
    assert stream_tile(1024, 12288, 4) == PACKED_TILE
    # rows or slots that are not whole tiles; rows of one tile; a block
    # the tile cannot hold
    assert [stream_tile(*shape) for shape in (
        (1024 + 128, 12288), (1024, 12288 + 128), (256, 12288),
        (1024, 12288, 3))] == [0, 0, 0, 0]
    q = jnp.zeros((64, 4 * DIM), jnp.bfloat16)
    kv = jnp.zeros((64, DIM), jnp.bfloat16)
    lens = jnp.asarray([32, 20], jnp.int32)
    with pytest.raises(ValueError, match="regime"):
        flash_attention_packed(q[:60], kv[:60], kv[:60], lens, WIDTH, 4,
                               BLOCK)
    with pytest.raises(ValueError, match="regime"):
        flash_attention_packed(q, kv, kv, lens, WIDTH, 4, BLOCK,
                               block_causal=3)
    with pytest.raises(ValueError, match="key/value heads"):
        flash_attention_packed(q, kv[:32], kv[:32], lens, WIDTH, 4, BLOCK)
    with pytest.raises(ValueError, match="not a number of keys"):
        flash_attention_packed(q, kv, kv, lens, WIDTH, 4, BLOCK, window=-1)


# What a compact 4 x 512 prefill of each preset compiles to: its
# grouped-query layers' packed paths (laguna-tiny: three sliding layers and
# two full ones; sdar-tiny's block-diffusion prefill: three; granite-tiny:
# its one attention layer among the state-space ones)
PACKED_PATHS = {
    "laguna-tiny": {"window_causal_packed": 3, "block_causal_packed": 2},
    "sdar-tiny": {"block_causal_packed": 3},
    "granite-tiny": {"block_causal_packed": 1},
}


@pytest.mark.parametrize("preset", list(PACKED_PATHS))
def test_the_compact_prefill_records_the_packed_path(preset):
    """The compile record of a compact prefill names the packed paths, one
    a grouped-query layer, and nothing of the ``[B, S]`` kernel's."""
    from music_analyst_tpu.engines.sentiment import get_backend
    from music_analyst_tpu.models import llama

    clf = get_backend(preset, seed=0)
    cfg = clf.config
    ids = jnp.asarray(np.random.default_rng(0).integers(
        16, cfg.offline_vocab_size, (4, 512)), jnp.int32)
    lens = jnp.asarray([300, 41, 256, 100], jnp.int32)
    assert llama.runs_compact(cfg, ids.shape, 768)
    if cfg.block_diffusion:
        program = clf._prefill
        program(clf.params, ids, lens, gen_blocks=4, prefill_capacity=768)
    else:
        program = clf._score_labels
        extra = {}
        if cfg.recurrent_state or cfg.mixed_layers:
            extra["probe_rows"] = jnp.asarray(np.minimum(clf.probe_rows, 3))
        program(clf.params, ids, lens, jnp.asarray(clf._label_ids),
                jnp.asarray(clf._label_lens), prefill_capacity=768, **extra)
    (record,) = [record for key, record in program.records.items()
                 if "int32[4, 512]" in key and "prefill_capacity" in key]
    paths = record.attention_paths
    assert {path: paths[path] for path in paths
            if path.endswith("_packed")} == PACKED_PATHS[preset]
    assert not {"window_causal", "block_causal"} & set(paths)
    assert record.traced_paths["gqa.compact"] == sum(
        PACKED_PATHS[preset].values())
