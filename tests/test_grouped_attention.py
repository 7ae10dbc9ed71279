"""Masked grouped-query attention in place (``models/layers.
dot_product_attention`` on ``ops/kv_cache.grouped_scores`` /
``grouped_values``): the same numbers as repeating the key and value heads
to the query heads and attending, no tensor of the repeated keys in the
program, and the compile record's ``traced_paths`` naming the layers that
took the grouped form.  CPU, small shapes."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from music_analyst_tpu.models.layers import dot_product_attention, window_mask

B, S, KV, KV_HEADS, D = 2, 5, 24, 2, 16
LABELS = 3
# float32: the interpreter's matmul is exact, only the order of the sums
# differs.  bfloat16: the result is rounded to bfloat16 and the
# probabilities are cast to it before the values, as the system does; the
# reference keeps both in float32 (outputs of magnitude <= 4: a step is
# 2**-6 at most).
TOLERANCE = {"float32": 2e-6, "bfloat16": 2.0 ** -6}


def _repeated_reference(q, k, v, mask, scale):
    """float32 repeat-then-attend: every key/value head copied to its
    group of query heads, the form ``dot_product_attention`` had."""
    group = q.shape[2] // k.shape[2]
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) * scale
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v,
                      precision=jax.lax.Precision.HIGHEST)


def _case(group, masking, dtype, seed=0):
    """``attend``: ``attend(fn)`` runs ``fn(q, k, v, mask, scale)`` on the
    case's inputs as its caller does."""
    heads = KV_HEADS * group
    rng = np.random.default_rng(seed + group)
    lead = (LABELS,) if masking == "vmap" else ()
    q, k, v = (jnp.asarray(rng.normal(size=lead + shape), dtype)
               for shape in ((B, S, heads, D), (B, KV, KV_HEADS, D),
                             (B, KV, KV_HEADS, D)))
    lengths = jnp.asarray([KV - S - 3, 9])
    # a continuation's slots: the prompt's up to its length, then S new
    # ones at the cache's end whose positions follow the row's length
    slot = jnp.arange(KV)[None, :]
    new = slot >= KV - S
    key_positions = jnp.where(new, lengths[:, None] + slot - (KV - S), slot)
    positions = lengths[:, None] + jnp.arange(S)[None, :]
    mask = ((slot < lengths[:, None]) | new)[:, None, None, :] & (
        key_positions[:, None, None, :] <= positions[:, None, :, None])
    if masking == "heads_all":
        # every head its own keys: a head's order in the group shows
        seen = rng.random((B, heads, S, KV)) < 0.6
        seen[..., 0] = True
        mask = mask & jnp.asarray(seen)
    elif masking == "window":
        mask = window_mask(mask, 6, S, KV, positions, key_positions)
    scale = D ** -0.5

    def attend(fn):
        if masking == "vmap":
            return jax.vmap(lambda q, k, v: fn(q, k, v, mask, scale))(q, k, v)
        return fn(q, k, v, mask, scale)

    return attend


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("masking", ["heads_one", "heads_all", "window",
                                     "vmap"])
@pytest.mark.parametrize("group", [1, 4, 6, 9])
def test_grouped_attention_is_repeat_then_attend(group, masking, dtype):
    """A mask of head axis 1 and one of head axis ``H``, a sliding window
    over a continuation's key positions, and the label passes' ``vmap``
    over three labels (each its own cache), at G = 1 (multi-head), 4, 6
    and 9 query heads a key head."""
    attend = _case(group, masking, jnp.dtype(dtype))
    got = attend(dot_product_attention)
    want = attend(_repeated_reference)
    assert got.dtype == jnp.dtype(dtype)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=0,
                               atol=TOLERANCE[dtype])


@pytest.mark.parametrize("labels", [0, LABELS])
def test_no_repeated_copy_of_the_keys_in_the_program(labels):
    """G = 9, one query position over a cache of 40 slots (a label pass):
    no ``[B, L, H, D]`` tensor of the keys, nor the ``[labels, B, L, H,
    D]`` broadcast a ``vmap`` over the labels made of it, in the lowered
    program or in what XLA compiles; the repeated form has it."""
    heads, length = KV_HEADS * 9, 40
    lead = (labels,) if labels else ()
    q = jax.ShapeDtypeStruct(lead + (B, 1, heads, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct(lead + (B, length, KV_HEADS, D), jnp.bfloat16)
    mask = jnp.ones((B, 1, 1, length), bool)
    repeated = lead + (B, length, heads, D)

    def texts(fn):
        call = lambda q, k, v: fn(q, k, v, mask, D ** -0.5)  # noqa: E731
        if labels:
            call = jax.vmap(call)
        lowered = jax.jit(call).lower(q, kv, kv)
        return lowered.as_text(), lowered.compile().as_text()

    stablehlo = "tensor<" + "x".join(map(str, repeated))
    hlo = "[" + ",".join(map(str, repeated)) + "]"
    lowered, compiled = texts(dot_product_attention)
    assert stablehlo not in lowered
    assert hlo not in compiled
    lowered, compiled = texts(_repeated_reference)
    assert stablehlo in lowered and hlo in compiled


@pytest.mark.parametrize("model, layers", [
    ("laguna-tiny", 5),       # five grouped-query layers of two kinds
    ("granite-tiny", 1),      # one attention layer among Mamba-2 layers
    ("llama3-tiny", 4),       # two layers, prefill and label passes
    ("kanana-tiny", 0),       # MLA: models/mla.py
    ("distilbert-tiny", 0),   # the encoder: whole-row kernel, G = 1
])
def test_compile_record_counts_the_grouped_layers(model, layers):
    """``traced_paths`` ``gqa.grouped`` in the compile record of one step
    of the model's program: the layers whose masked attention met fewer
    key heads than query heads (a label pass under ``vmap`` or ``lax.map``
    is traced once)."""
    from music_analyst_tpu.engines.sentiment import get_backend
    from music_analyst_tpu.profiling.compile import ProfiledFunction

    clf = get_backend(model, seed=0)
    lyrics = ["love and rain all night " * 9, "you me", "the road " * 30]
    clf.collect(clf.launch(clf.transfer(clf.prepare(lyrics))))
    records = [record for program in vars(clf).values()
               if isinstance(program, ProfiledFunction)
               for record in program.records.values()]
    assert records
    for record in records:
        assert record.traced_paths.get("gqa.grouped", 0) == layers
