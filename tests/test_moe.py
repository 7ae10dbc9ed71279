"""Sparse MoE dispatch vs the dense all-experts oracle.

The sparse path (token-choice top-k, capacity-bounded scatter/gather,
``models/moe.py``) must be the same *math* as the dense path — the only
sanctioned divergence is capacity drops.  With ``capacity_factor >=
n_experts`` no assignment can ever drop, so sparse must reproduce dense
(nearly) exactly; at production factors the divergence is bounded by the
dropped router mass.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from music_analyst_tpu.models.moe import MoESwiGLU

E, H, D, K = 4, 16, 8, 2


def _pair(dispatch_kwargs_a, dispatch_kwargs_b, x, seed=0):
    a = MoESwiGLU(E, H, top_k=K, dtype=jnp.float32, **dispatch_kwargs_a)
    b = MoESwiGLU(E, H, top_k=K, dtype=jnp.float32, **dispatch_kwargs_b)
    params = a.init(jax.random.key(seed), x)["params"]
    return a.apply({"params": params}, x), b.apply({"params": params}, x)


def test_sparse_lossless_capacity_matches_dense():
    x = jax.random.normal(jax.random.key(1), (2, 6, D), jnp.float32)
    dense, sparse = _pair(
        {"dispatch": "dense"},
        {"dispatch": "sparse", "capacity_factor": float(E)},
        x,
    )
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(sparse), rtol=1e-5, atol=1e-5
    )


def test_sparse_param_tree_identical_to_dense():
    """Dispatch is a compute strategy, not an architecture: checkpoints
    trained dense load into sparse and vice versa."""
    x = jnp.zeros((1, 4, D), jnp.float32)
    dense = MoESwiGLU(E, H, top_k=K, dispatch="dense")
    sparse = MoESwiGLU(E, H, top_k=K, dispatch="sparse")
    tree_a = jax.tree_util.tree_structure(
        dense.init(jax.random.key(0), x)["params"]
    )
    tree_b = jax.tree_util.tree_structure(
        sparse.init(jax.random.key(0), x)["params"]
    )
    assert tree_a == tree_b


def test_capped_capacity_divergence_bounded_by_dropped_mass():
    """At capacity_factor=1.0 drops can occur; the output still matches
    dense on every token whose assignments all fit."""
    x = jax.random.normal(jax.random.key(2), (2, 16, D), jnp.float32)
    dense, sparse = _pair(
        {"dispatch": "dense"},
        {"dispatch": "sparse", "capacity_factor": 1.0},
        x,
    )
    dense, sparse = np.asarray(dense), np.asarray(sparse)
    # Token-level: a token either matches dense (all assignments kept) or
    # lost some router mass (dropped expert) — never garbage.
    per_token = np.abs(dense - sparse).max(axis=-1).reshape(-1)
    matching = per_token < 1e-5
    assert matching.mean() >= 0.5  # most tokens fit at factor 1.0
    # Divergent tokens are bounded by the norm dense assigns (lost mass <=
    # full contribution), not unbounded garbage.
    assert np.abs(sparse).max() <= np.abs(dense).max() * 3 + 1.0


def test_sparse_is_differentiable():
    x = jax.random.normal(jax.random.key(3), (1, 8, D), jnp.float32)
    moe = MoESwiGLU(E, H, top_k=K, dtype=jnp.float32, dispatch="sparse")
    params = moe.init(jax.random.key(0), x)["params"]

    def loss(p):
        return jnp.sum(moe.apply({"params": p}, x) ** 2)

    grads = jax.grad(loss)(params)
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in leaves)
    # Expert weights receive gradient (dispatch routes real tokens).
    assert any(float(np.abs(np.asarray(g)).sum()) > 0 for g in leaves)


def test_sparse_flop_scaling():
    """The point of sparse dispatch: expert matmul work is k*cf per token,
    not E per token.  Count contraction sizes via the buffer shape."""
    T = 64
    x = jnp.zeros((1, T, D), jnp.float32)
    moe = MoESwiGLU(E, H, top_k=K, dispatch="sparse", capacity_factor=1.25)
    params = moe.init(jax.random.key(0), x)["params"]
    jaxpr = jax.make_jaxpr(
        lambda p: moe.apply({"params": p}, x)
    )(params)
    # Single source of truth for the slot count (no duplicated formula).
    from music_analyst_tpu.models.moe import moe_capacity

    capacity = moe_capacity(T, K, E, 1.25)
    buffer_rows = E * capacity
    dense_rows = E * T
    # Expert-matmul rows scale as k*cf per token instead of E: the ratio
    # is (k*cf)/E — an E/(k*cf)-fold FLOP drop (1.6x here; 3.2x at E=8).
    assert buffer_rows / dense_rows <= (K * 1.25) / E * 1.1
    # and the jaxpr indeed materializes the [E, capacity, H] intermediate
    assert f"{E},{capacity},{H}" in str(jaxpr).replace(" ", "").replace(
        "(", ""
    ).replace(")", "")


def test_int8_sparse_matches_int8_dense_at_lossless_capacity():
    """Both dispatches quantize per (expert, row), so with no capacity
    drops the quantized math is identical up to f32 reduction order —
    int8 must not widen the sparse/dense gap."""
    x = jax.random.normal(jax.random.key(4), (2, 6, D), jnp.float32)
    dense, sparse = _pair(
        {"dispatch": "dense", "quant": "int8"},
        {"dispatch": "sparse", "quant": "int8",
         "capacity_factor": float(E)},
        x,
    )
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(sparse), rtol=1e-4, atol=1e-4
    )


def test_int8_tracks_float_moe():
    """Per-expert int8 expert einsums stay inside the symmetric-int8
    error bound relative to the float module on the same params."""
    x = jax.random.normal(jax.random.key(5), (2, 8, D), jnp.float32)
    f32, q = _pair(
        {"dispatch": "sparse"},
        {"dispatch": "sparse", "quant": "int8"},
        x,
    )
    f32, q = np.asarray(f32), np.asarray(q)
    corr = np.corrcoef(f32.ravel(), q.ravel())[0, 1]
    assert corr > 0.99, corr
    # Not bit-identical (that would mean the int8 path never ran).
    assert np.abs(f32 - q).max() > 0


def test_int8_param_tree_identical_to_float():
    """quant is a compute strategy like dispatch: float checkpoints load
    into the int8 module unchanged."""
    x = jnp.zeros((1, 4, D), jnp.float32)
    tree_a = jax.tree_util.tree_structure(
        MoESwiGLU(E, H, top_k=K).init(jax.random.key(0), x)["params"]
    )
    tree_b = jax.tree_util.tree_structure(
        MoESwiGLU(E, H, top_k=K, quant="int8").init(
            jax.random.key(0), x
        )["params"]
    )
    assert tree_a == tree_b


def test_bad_dispatch_rejected():
    x = jnp.zeros((1, 4, D), jnp.float32)
    moe = MoESwiGLU(E, H, dispatch="typo")
    with pytest.raises(ValueError, match="dispatch"):
        moe.init(jax.random.key(0), x)


def test_capacity_ceils_not_truncates():
    """Decode-scale token counts keep their capacity headroom: the factor
    product ceils (2.5 -> 3 slots), never truncates back to fair share."""
    from music_analyst_tpu.models.moe import moe_capacity

    assert moe_capacity(4, 2, 4, 1.25) == 3
    assert moe_capacity(64, 2, 4, 1.0) == 32
    assert moe_capacity(64, 2, 4, 1.25) == 40
    assert moe_capacity(0, 2, 4, 1.25) == 1
    assert moe_capacity(16, 2, 4, 4.0) == 32  # lossless >= T*k/E*E


def test_sparse_moe_inside_classifier_forward():
    """Sparse dispatch composes with the real model: zero-shot scoring
    (vmapped label continuation) and scan generation both run with an
    MoE FFN, honoring the empty-lyric rule."""
    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )

    cfg = LlamaConfig(
        vocab_size=300, dim=32, n_layers=1, n_heads=4, n_kv_heads=2,
        hidden_dim=64, rope_theta=1e4, max_seq_len=256, dtype="float32",
        n_experts=4, moe_top_k=2,
    )
    clf = LlamaZeroShotClassifier(config=cfg, max_prompt_len=128)
    labels = clf.classify_batch(["love and rain", "", "pain " * 20])
    assert labels[1] == "Neutral"
    assert all(l in ("Positive", "Neutral", "Negative") for l in labels)
    outs = clf.generate_batch(["say hi", "la"], max_new_tokens=4)
    assert len(outs) == 2


# ---------------------------------------------------------------------------
# The compact token set of a prefill that knows its rows' lengths
# (``RealPositions``, ``compact_capacity``, ``SigmoidRoutedMoE(x, compact)``)

# (lengths of a [rows, 16] step, the rung that holds them)
_RAGGED = ([16, 1, 9, 5], 32)       # a full row and a one-token row: N = 31
_EXACT = ([8, 3, 16, 5], 32)        # the real tokens are exactly a rung
_SPARSE = ([1, 1, 2, 1], 8)         # one rung holds them all
_FULL = ([16, 16, 16, 16], 64)      # every row full: the uncompacted step
_WIDTH = 16


@pytest.mark.parametrize("lengths,rung", [
    _RAGGED, _EXACT, _SPARSE, _FULL, ([16, 16, 16, 15], 64),
    ([9, 8, 8, 8], 40)], ids=["ragged", "exact", "sparse", "full",
                              "one-short-of-full", "one-past-a-rung"])
def test_compact_capacity_is_the_smallest_eighth_that_holds_the_tokens(
        lengths, rung):
    from music_analyst_tpu.models.compact_stream import compact_capacity

    positions = len(lengths) * _WIDTH
    assert compact_capacity(sum(lengths), len(lengths), _WIDTH) == rung
    assert sum(lengths) <= rung <= positions
    assert rung % (positions // 8) == 0


def test_compact_capacity_never_passes_the_step_and_holds_an_empty_one():
    from music_analyst_tpu.models.compact_stream import compact_capacity

    assert compact_capacity(0, 1, 64) == 8     # one rung, never zero slots
    assert compact_capacity(64, 1, 64) == 64
    assert compact_capacity(20, 1, 20) == 20   # 20 positions: rungs of 3
    assert compact_capacity(7, 1, 20) == 9
    assert {compact_capacity(n, 32, 1024) for n in range(1, 32 * 1024 + 1,
                                                          97)} == {
        4096 * i for i in range(1, 9)}         # at most eight programs


@pytest.mark.parametrize("rows,width,rungs", [
    (16, 1024, [2048 * i for i in range(1, 9)]),
    (32, 1024, [4096 * i for i in range(1, 9)]),
    (64, 1024, [8192 * i for i in range(1, 9)]),
    (13, 1024, [1792, 3328, 5120, 6656, 8448, 9984, 11776, 13312]),
    (13, 512, [1024, 1792, 2560, 3328, 4352, 5120, 5888, 6656]),
], ids=["16x1024", "32x1024", "64x1024", "13x1024", "13x512"])
def test_compact_capacity_at_a_kernel_width_is_whole_tiles(rows, width,
                                                           rungs):
    """At a width the packed kernels take, every rung is whole 256-slot
    tiles: the cells' steps (16, 32 and 64 rows of 1,024) keep their
    eighths as they were, and an odd number of rows (a corpus job's last
    batch) has each eighth rounded up to whole tiles, never past the
    step's own positions; still at most eight programs, the last the
    step's rows x width."""
    from music_analyst_tpu.models.compact_stream import compact_capacity

    got = sorted({compact_capacity(n, rows, width)
                  for n in range(0, rows * width + 1, 13)}
                 | {compact_capacity(rows * width, rows, width)})
    assert got == rungs
    assert all(rung % 256 == 0 for rung in got)


@pytest.mark.parametrize("lengths,capacity", [
    _RAGGED, _EXACT, _SPARSE, ([16, 1, 9, 5], 48)],
    ids=["ragged", "exact", "sparse", "a-rung-too-many"])
def test_real_positions_index_is_row_major_and_its_own_inverse(
        lengths, capacity):
    from music_analyst_tpu.models.compact_stream import RealPositions

    index = RealPositions.of(jnp.asarray(lengths, jnp.int16), _WIDTH,
                             capacity)
    want = [row * _WIDTH + pos for row, n in enumerate(lengths)
            for pos in range(n)]
    n_real = len(want)
    assert np.asarray(index.source)[:n_real].tolist() == want
    assert np.asarray(index.valid).tolist() == (
        [True] * n_real + [False] * (capacity - n_real))
    # fillers hold a real position's copy: any index a gather may read
    assert set(np.asarray(index.source)[n_real:].tolist()) <= {want[-1]}
    x = jax.random.normal(jax.random.key(0), (len(lengths), _WIDTH, 3))
    back = np.asarray(index.put_back(index.gather(x)))
    real = np.arange(_WIDTH)[None, :] < np.asarray(lengths)[:, None]
    assert (np.asarray(index.real) == real).all()
    assert (back[real] == np.asarray(x)[real]).all()
    assert (back[~real] == 0).all()


def _routed(dtype):
    from music_analyst_tpu.models.moe import SigmoidRoutedMoE

    return SigmoidRoutedMoE(8, 16, 2, n_shared=1, routed_scaling_factor=2.0,
                            dtype=dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("lengths,capacity", [
    _RAGGED, _EXACT, _SPARSE, ([16, 1, 9, 5], 48)],
    ids=["ragged", "exact", "sparse", "a-rung-too-many"])
def test_compact_experts_equal_the_full_layer_on_every_real_position(
        lengths, capacity, dtype):
    """Router, sort, grouped matmuls, weighted sum and shared experts on
    the real positions alone give what the full layer gives there (a row
    of a grouped matmul depends on that row and its expert alone), the
    same experts chosen; padding receives zeros and is counted nowhere."""
    from music_analyst_tpu.models.compact_stream import RealPositions

    layer = _routed(dtype)
    rows = len(lengths)
    x = jax.random.normal(jax.random.key(3), (rows, _WIDTH, 12), dtype)
    params = layer.init(jax.random.key(4), x[:1, :2])
    full, sown_full = layer.apply(params, x, mutable=["intermediates"])
    index = RealPositions.of(jnp.asarray(lengths), _WIDTH, capacity)
    got, sown = layer.apply(params, x, index, mutable=["intermediates"])
    assert got.shape == full.shape and got.dtype == full.dtype
    real = np.asarray(index.real)
    full, got = np.asarray(full, np.float32), np.asarray(got, np.float32)
    step = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-6
    assert (np.abs(got - full)[real]
            <= step * np.maximum(np.abs(full)[real], 1.0)).all()
    assert (got[~real] == 0).all() and np.abs(got[real]).max() > 0.1
    chosen_full = np.asarray(sown_full["intermediates"]["chosen"][0])
    chosen = np.asarray(sown["intermediates"]["chosen"][0])
    assert chosen.shape == (rows, _WIDTH, 2)
    assert (chosen[real] == chosen_full[real]).all()
    assert (chosen[~real] == 0).all()
    # (b) the load counts the real positions' assignments, fillers and
    # padding uncounted: exactly the full layer's load over those positions
    load = np.asarray(sown["intermediates"]["expert_load"][0])
    assert load.sum() == sum(lengths) * 2
    assert load.tolist() == np.bincount(
        chosen_full[real].reshape(-1), minlength=8).tolist()
    assert np.asarray(
        sown_full["intermediates"]["expert_load"][0]).sum() == rows * 16 * 2


def test_fillers_belong_to_no_expert_and_cannot_reach_a_real_position():
    """A filler's assignments sort behind the last group: poisoning what
    the filler slots hold changes no real position's result."""
    from music_analyst_tpu.models.compact_stream import RealPositions

    layer = _routed(jnp.float32)
    lengths, capacity = _RAGGED
    x = jax.random.normal(jax.random.key(5), (4, _WIDTH, 12), jnp.float32)
    params = layer.init(jax.random.key(6), x[:1, :2])
    index = RealPositions.of(jnp.asarray(lengths), _WIDTH, capacity)
    want = layer.apply(params, x, index)
    # the filler now copies a padding position that holds an infinity
    poisoned = x.at[1, 5].set(jnp.inf)
    moved = index._replace(source=jnp.where(index.valid, index.source,
                                            1 * _WIDTH + 5))
    got = layer.apply(params, poisoned, moved)
    assert bool(jnp.isfinite(got).all())
    assert (np.asarray(got) == np.asarray(want)).all()


@pytest.mark.parametrize("lengths,capacity", [
    _RAGGED, _EXACT, ([16, 1, 9, 5], 48)],
    ids=["ragged", "exact", "a-rung-too-many"])
def test_packed_experts_take_and_return_the_token_set_itself(
        lengths, capacity):
    """``packed``: the caller's stream IS the compact token set (the
    latent blocks under ``LlamaModel``'s compact prefill).  Its result on
    the real slots is the gathered form's before the put-back, nothing is
    ``[B, S, dim]`` but the sown ``chosen``, and a filler (whose rows of
    the grouped matmuls belong to no group and are undefined) comes out
    with the shared experts' output alone: it stays in the stream, and the
    prefill kernel needs it finite."""
    from music_analyst_tpu.models.layers import SwiGLU
    from music_analyst_tpu.models.compact_stream import RealPositions

    layer = _routed(jnp.float32)
    rows, n_real = len(lengths), sum(lengths)
    x = jax.random.normal(jax.random.key(7), (rows, _WIDTH, 12), jnp.float32)
    params = layer.init(jax.random.key(8), x[:1, :2])
    index = RealPositions.of(jnp.asarray(lengths), _WIDTH, capacity)
    want, sown_want = layer.apply(params, x, index, mutable=["intermediates"])
    stream = index.gather(x)[None]
    got, sown = layer.apply(params, stream, index, packed=True,
                            mutable=["intermediates"])
    assert got.shape == stream.shape == (1, capacity, 12)
    np.testing.assert_allclose(np.asarray(index.put_back(got[0])),
                               np.asarray(want), rtol=0, atol=1e-6)
    for name in ("chosen", "expert_load"):
        assert (np.asarray(sown["intermediates"][name][0]) == np.asarray(
            sown_want["intermediates"][name][0])).all()
    shared = SwiGLU(16, dtype=jnp.float32, name="shared_experts").apply(
        {"params": params["params"]["shared_experts"]}, stream[0, n_real:])
    np.testing.assert_allclose(np.asarray(got[0, n_real:]),
                               np.asarray(shared), rtol=0, atol=1e-6)


@pytest.mark.parametrize("partial", [False, True], ids=["whole", "held"])
@pytest.mark.parametrize("top_k", [2, 6, 8])
def test_grouped_rows_return_to_token_order_and_are_summed_in_float32(
        top_k, partial, monkeypatch):
    """The way back from the grouped matmuls, one form for every
    ``top_k``: a real token's result is ``sum_j w[t, j] * expert(x[t])``
    over the choices held here, each expert's bfloat16 row taken to
    float32 and weighted and added there, as a plain per-token loop gives
    it.  Rows of the grouped matmuls that belong to no group are undefined:
    they are NaN here, and with ``partial`` an assignment to an absent
    expert still counts as exactly zero (a token with none held comes out
    zero), while a filler's own row of the whole layer's form stays
    undefined."""
    import flax.linen as nn

    from music_analyst_tpu.models import moe

    n_experts, dim, hidden, tokens, fillers = 16, 16, 8, 37, 5

    def few_bits(key, shape, scale):
        # quarter steps: every matmul's float32 sum is exact in any order,
        # so its bfloat16 row is the same one row at a time
        return (jnp.round(jax.random.normal(key, shape) * scale * 4) / 4
                ).astype(jnp.bfloat16)

    keys = jax.random.split(jax.random.key(top_k), 7)
    x = few_bits(keys[0], (tokens, dim), 1.0)
    gate_w = few_bits(keys[1], (n_experts, dim, hidden), 0.5)
    up_w = few_bits(keys[2], (n_experts, dim, hidden), 0.5)
    down_w = few_bits(keys[3], (n_experts, hidden, dim), 0.5)
    chosen = jnp.argsort(jax.random.uniform(keys[4], (tokens, n_experts)),
                         axis=-1)[:, :top_k].astype(jnp.int32)
    if partial:  # about a third of the assignments are another chip's
        absent = jax.random.uniform(keys[5], chosen.shape) < 0.35
        absent = absent.at[0].set(True).at[1, 1:].set(True)
        chosen = jnp.where(absent, n_experts, chosen)
    chosen = chosen.at[tokens - fillers:].set(n_experts)
    weights = jax.random.uniform(keys[6], (tokens, top_k), jnp.float32,
                                 0.05, 1.0)

    ragged_dot = jax.lax.ragged_dot

    def undefined_behind_the_groups(lhs, rhs, group_sizes, **kwargs):
        out = ragged_dot(lhs, rhs, group_sizes, **kwargs)
        grouped = jnp.arange(lhs.shape[0]) < group_sizes.sum()
        return jnp.where(grouped[:, None], out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", undefined_behind_the_groups)
    grouped = moe.grouped_experts_held if partial else moe.grouped_experts
    got = np.asarray(grouped(x, chosen, weights, gate_w, up_w, down_w))
    assert got.shape == (tokens, dim) and got.dtype == np.float32

    def expert_row(t, e):
        gate, up = jnp.dot(x[t], gate_w[e]), jnp.dot(x[t], up_w[e])
        assert gate.dtype == jnp.bfloat16
        return np.asarray(jnp.dot(nn.silu(gate) * up, down_w[e]), np.float32)

    chosen, weights = np.asarray(chosen), np.asarray(weights)
    real = tokens - fillers
    for t in range(real):
        want, size = np.zeros(dim, np.float32), np.zeros(dim, np.float32)
        for j in range(top_k):
            if chosen[t, j] < n_experts:
                term = weights[t, j] * expert_row(t, chosen[t, j])
                want, size = want + term, size + np.abs(term)
        assert np.isfinite(got[t]).all()
        # the order of the k float32 additions is free, nothing else is
        assert (np.abs(got[t] - want) <= top_k * 2.0 ** -23 * size).all()
    assert np.abs(got[:real]).max() > 0.1
    if partial:
        assert (got[0] == 0).all() and (got[real:] == 0).all()
        held_once = weights[1, 0] * expert_row(1, chosen[1, 0])
        assert (got[1] == held_once).all()
    else:
        assert np.isnan(got[real:]).all()
