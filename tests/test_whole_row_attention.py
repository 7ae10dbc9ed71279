"""The whole-row attention kernel (ops/whole_row_attention.py): numerics
against the dense path, the whole DistilBERT forward, which inputs take
it, and the meshed forward.  CPU, Pallas interpreter, small shapes; what
Mosaic makes of the kernel is tests/test_mosaic_aot.py, its numerics on
the chip are chip runs (PERF.md)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from music_analyst_tpu.models import distilbert
from music_analyst_tpu.models.distilbert import (
    DistilBertClassifier,
    DistilBertConfig,
    DistilBertForSentiment,
)
from music_analyst_tpu.models.layers import (
    KVCache,
    MultiHeadAttention,
    causal_mask,
    dot_product_attention,
    padding_mask,
)
from music_analyst_tpu.ops.whole_row_attention import (
    _whole_row_call,
    whole_row_attention,
    whole_row_block_rows,
)
from music_analyst_tpu.telemetry import get_telemetry

# Tolerances, stated once.  float32: the interpreter's matmul is exact, so
# only the order of the softmax's sums differs.  bfloat16: the kernel and
# the dense form both keep the scores in float32, so they differ in the
# order of the sums and the result's rounding: at most one bfloat16 step of
# an output under 2 (2**-7; 2**-8 is the most these cases read); against a
# float32 reference the kernel must be no farther away than the dense path
# is, give or take an eighth of a step at 4 (2**-9).
F32_ATOL = 2e-6
BF16_ATOL = 2.0 ** -7 + 1e-6
BF16_REF_SLACK = 2.0 ** -9


def _qkv(rows, seq, heads, head_dim, dtype, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    return [
        jax.random.normal(k, (rows, seq, heads, head_dim), jnp.float32)
        .astype(dtype)
        for k in keys
    ]


def _lengths(kind, rows, seq, seed=0):
    if kind == "one":
        return jnp.ones((rows,), jnp.int32)
    if kind == "full":
        return jnp.full((rows,), seq, jnp.int32)
    mixed = np.random.default_rng(seed).integers(1, seq + 1, rows)
    mixed[0], mixed[-1] = 1, seq  # both ends present whatever the draw
    return jnp.asarray(mixed, jnp.int32)


def _f32_reference(q, k, v, lengths):
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    return dot_product_attention(q, k, v, padding_mask(lengths, q.shape[1]))


def _kernel(q, k, v, lengths):
    """The kernel itself at any row length: the public function admits
    only lane-filling rows (a rule of speed, not of correctness), and
    interpreting S = 128 everywhere would cost the suite minutes."""
    return _whole_row_call(q, k, v, lengths, block_rows=8, interpret=True)


# rows: 1, 7 (one short block), 19 and 306-like 21 (= 2 x 8 + a tail the
# block of 8 does not divide).
@pytest.mark.parametrize("lengths_kind", ["one", "mixed", "full"])
@pytest.mark.parametrize(
    "rows,seq,heads,head_dim,dtype",
    [
        (1, 128, 12, 64, jnp.bfloat16),
        (7, 32, 12, 64, jnp.bfloat16),
        (19, 64, 12, 64, jnp.bfloat16),
        (21, 128, 4, 16, jnp.bfloat16),
        (1, 32, 4, 16, jnp.float32),
        (7, 128, 4, 16, jnp.float32),
        (19, 32, 12, 64, jnp.float32),
        (21, 64, 12, 64, jnp.float32),
    ],
    ids=lambda x: getattr(x, "__name__", str(x)),
)
def test_kernel_matches_dense(rows, seq, heads, head_dim, dtype, lengths_kind):
    q, k, v = _qkv(rows, seq, heads, head_dim, dtype, seed=rows + seq)
    lengths = _lengths(lengths_kind, rows, seq, seed=rows)
    got = _kernel(q, k, v, lengths)
    if seq == 128:  # what the public function adds is the choice of block
        np.testing.assert_array_equal(
            got, whole_row_attention(q, k, v, lengths)
        )
    want = dot_product_attention(q, k, v, padding_mask(lengths, seq))
    assert got.shape == want.shape and got.dtype == want.dtype
    got32, want32 = got.astype(jnp.float32), want.astype(jnp.float32)
    assert bool(jnp.isfinite(got32).all())
    if dtype == jnp.float32:
        np.testing.assert_allclose(got32, want32, rtol=0, atol=F32_ATOL)
        return
    np.testing.assert_allclose(got32, want32, rtol=0, atol=BF16_ATOL)
    ref = _f32_reference(q, k, v, lengths)
    assert float(jnp.abs(got32 - ref).max()) <= (
        float(jnp.abs(want32 - ref).max()) + BF16_REF_SLACK
    )


def test_kernel_refuses_what_it_cannot_take():
    q, k, v = _qkv(2, 32, 4, 16, jnp.float32)  # S leaves lanes empty
    with pytest.raises(ValueError, match="whole_row_block_rows"):
        whole_row_attention(q, k, v, jnp.ones((2,), jnp.int32))
    q, k, v = _qkv(2, 128, 4, 16, jnp.float32)
    with pytest.raises(ValueError, match="self-attention"):
        whole_row_attention(q, k[:, :64], v[:, :64], jnp.ones((2,), jnp.int32))


@pytest.mark.parametrize(
    "seq,heads,head_dim,dtype,fits",
    [
        (128, 12, 64, "bfloat16", True),    # the corpus job's shape
        (256, 12, 64, "bfloat16", True),
        (384, 12, 64, "bfloat16", True),    # one row a step
        (512, 12, 64, "bfloat16", False),   # the scores no longer fit
        (1024, 4, 16, "bfloat16", False),
        (128, 12, 64, "float32", True),
        (384, 12, 64, "float32", False),
        (32, 12, 64, "bfloat16", False),    # a length bucket: empty lanes
        (64, 12, 64, "bfloat16", False),
        (136, 4, 16, "float32", False),
        (128, 4, 8, "bfloat16", False),     # a head off the bf16 tile
        (128, 4, 8, "float32", True),
    ],
)
def test_the_limit_is_arithmetic_on_the_shape(seq, heads, head_dim, dtype, fits):
    assert bool(whole_row_block_rows(seq, heads, head_dim, dtype)) is fits


# ------------------------------------------------------- the whole model

def _tiny(dtype, **kw):
    return dataclasses.replace(DistilBertConfig.tiny(), dtype=dtype, **kw)


def _wide(dtype):
    # DistilBERT's attention geometry (12 heads of 64), everything else cut
    return DistilBertConfig(
        vocab_size=512, dim=768, n_layers=2, n_heads=12, hidden_dim=128,
        max_positions=128, dtype=dtype,
    )


def _logits(cfg, ids, lengths, monkeypatch, dense):
    if dense:
        monkeypatch.setattr(
            distilbert, "whole_row_block_rows", lambda *a, **k: 0
        )
    model = DistilBertForSentiment(cfg)
    params = model.init(jax.random.key(3), ids[:1], lengths[:1])["params"]
    out = model.apply({"params": params}, ids, lengths)
    monkeypatch.undo()
    return out


@pytest.mark.parametrize(
    "make,dtype,seq,atol",
    [
        (_tiny, "float32", 128, 1e-5),
        (_wide, "float32", 128, 1e-5),
        # bfloat16 activations: one step of the hidden state's magnitude
        # per layer, through the head: logits of O(1) within 0.05
        (_tiny, "bfloat16", 128, 5e-2),
        (_wide, "bfloat16", 128, 5e-2),
    ],
    ids=["tiny-f32", "wide-f32", "tiny-bf16", "wide-bf16"],
)
def test_distilbert_logits_match_the_dense_forward(
    make, dtype, seq, atol, monkeypatch
):
    cfg = make(dtype)
    rng = np.random.default_rng(7)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (11, seq)), jnp.int32)
    lengths = _lengths("mixed", 11, seq, seed=7)
    dense = _logits(cfg, ids, lengths, monkeypatch, dense=True)
    kernel = _logits(cfg, ids, lengths, monkeypatch, dense=False)
    assert bool(jnp.isfinite(kernel).all())
    np.testing.assert_allclose(kernel, dense, rtol=0, atol=atol)


# ------------------------------------------------- who takes the kernel

def _paths_of(fn):
    """Attention paths noted while ``fn`` traces: the telemetry counters
    ``attention.<path>`` (profiling/compile.note_attention_path)."""
    tel = get_telemetry()
    before = dict(tel.counters)
    fn()
    return {
        name.split(".", 1)[1]: n - before.get(name, 0)
        for name, n in tel.counters.items()
        if name.startswith("attention.") and n != before.get(name, 0)
    }


def _encoder_paths(cfg, seq, **apply_kw):
    model = DistilBertForSentiment(cfg)
    ids = jnp.zeros((4, seq), jnp.int32)
    lengths = jnp.full((4,), seq, jnp.int32)
    return _paths_of(
        lambda: jax.eval_shape(
            lambda: model.init(jax.random.key(0), ids, lengths, **apply_kw)
        )
    )


def test_flat_short_rows_take_the_kernel():
    cfg = _tiny("bfloat16")
    assert _encoder_paths(cfg, 128) == {"whole_row": cfg.n_layers}
    int8 = dataclasses.replace(cfg, quant="int8")  # projections differ only
    assert _encoder_paths(int8, 128) == {"whole_row": cfg.n_layers}


def test_rows_outside_the_limit_keep_dense():
    cfg = _tiny("bfloat16", max_positions=1024)
    assert _encoder_paths(cfg, 1024) == {"dense": cfg.n_layers}  # too long
    assert _encoder_paths(cfg, 32) == {"dense": cfg.n_layers}    # a bucket
    assert _encoder_paths(cfg, 12) == {"dense": cfg.n_layers}


def test_segments_keep_dense():
    cfg = _tiny("bfloat16")
    seg = jnp.ones((4, 128), jnp.int32)
    pos = jnp.zeros((4, 128), jnp.int32)
    paths = _encoder_paths(cfg, 128, positions=pos, segment_ids=seg)
    assert paths == {"dense": cfg.n_layers}


def test_flash_is_not_rerouted():
    cfg = _tiny("bfloat16", attn_impl="flash")
    assert _encoder_paths(cfg, 128) == {}


def _mha_paths(**call_kw):
    mha = MultiHeadAttention(n_heads=4, dtype=jnp.float32)
    x = jnp.zeros((2, 128, 64), jnp.float32)
    return _paths_of(
        lambda: jax.eval_shape(
            lambda: mha.init(jax.random.key(0), x, **call_kw)
        )
    )


def test_mask_arrays_causal_and_caches_keep_dense():
    lengths = jnp.full((2,), 128, jnp.int32)
    # an arbitrary mask array, with or without lengths beside it
    assert _mha_paths(mask=padding_mask(lengths, 128)) == {"dense": 1}
    assert _mha_paths(
        mask=padding_mask(lengths, 128), lengths=lengths
    ) == {"dense": 1}
    # causal (decoder prefill): always a mask array
    assert _mha_paths(mask=causal_mask(128, 128, 0), lengths=lengths) == {
        "dense": 1
    }
    # no mask at all (ring attention's local blocks, tests)
    assert _mha_paths() == {"dense": 1}
    # a cache (decode): S_q != S_kv
    cache = KVCache.zeros(2, 256, 4, 16, jnp.float32)
    assert _mha_paths(
        mask=causal_mask(128, 256, 0), cache=cache, lengths=lengths
    ) == {"dense": 1}
    # key padding by lengths alone is what the kernel is for
    assert _mha_paths(lengths=lengths) == {"whole_row": 1}


def test_outside_the_regime_the_program_is_the_dense_one(monkeypatch):
    """Long rows and packed rows lower to the text they lower to with the
    kernel's predicate forced off: no trace of the change in them."""
    cfg = _tiny("bfloat16", max_positions=1024)
    model = DistilBertForSentiment(cfg)

    def lowered(seq, **kw):
        ids = jnp.zeros((2, seq), jnp.int32)
        lengths = jnp.full((2,), seq, jnp.int32)
        params = jax.eval_shape(
            lambda: model.init(jax.random.key(0), ids, lengths, **kw)
        )["params"]
        return jax.jit(
            lambda p, i, n: model.apply({"params": p}, i, n, **kw)
        ).lower(params, ids, lengths).as_text()

    seg = dict(positions=jnp.zeros((2, 128), jnp.int32),
               segment_ids=jnp.ones((2, 128), jnp.int32))
    natural = [lowered(1024), lowered(64), lowered(128, **seg)]
    assert "_whole_row_call" not in "".join(natural)
    assert "_whole_row_call" in lowered(128)  # the marker does mark
    monkeypatch.setattr(distilbert, "whole_row_block_rows", lambda *a, **k: 0)
    assert natural == [lowered(1024), lowered(64), lowered(128, **seg)]


def test_compile_record_names_the_path_of_each_shape():
    """What a run's manifest carries (``profiling.compiles``): one record
    per compiled batch shape, each naming the path its layers took."""
    from music_analyst_tpu.profiling.compile import compile_records

    backend = DistilBertClassifier(config=_tiny("bfloat16"), max_len=128, seed=1)
    backend.classify_batch(["sun and rain"] * 3)
    backend.classify_batch(["sun and rain"] * 5)
    records = list(backend._forward.records.values())
    assert len(records) == 2  # 3 rows and 5 rows: two programs
    for record in records:
        assert record.attention_paths == {"whole_row": 2}
        assert record.as_dict() in compile_records()


# ------------------------------------------------------------- the mesh

TEXTS = ["love and sunshine", "tears and pain", "", "la la la " * 30] * 4


@pytest.mark.parametrize(
    "axes", [(("dp", 4),), (("dp", 2), ("tp", 2))], ids=["dp4", "dp2xtp2"]
)
def test_meshed_forward_equals_unmeshed_and_gathers_nothing(axes):
    from music_analyst_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(axes), devices=jax.devices()[:4])
    cfg = _tiny("float32")
    plain = DistilBertClassifier(config=cfg, max_len=128, seed=5)
    meshed = DistilBertClassifier(config=cfg, max_len=128, seed=5, mesh=mesh)

    _, (part,) = plain.submit(TEXTS)
    _, (mpart,) = meshed.submit(TEXTS)
    np.testing.assert_array_equal(np.asarray(part[1]), np.asarray(mpart[1]))
    np.testing.assert_allclose(
        np.asarray(part[2]), np.asarray(mpart[2]), rtol=0, atol=1e-6
    )

    _, placed = meshed.transfer(meshed.prepare(TEXTS))
    (_, _, arrays), = placed
    (record,) = meshed._forward.records.values()
    # per shard, not dense
    assert record.attention_paths == {"whole_row": cfg.n_layers}
    program = meshed._forward.lower(meshed.params, *arrays).compile().as_text()
    # the batch stays split over dp from the embedding to the result: the
    # kernel runs per shard and nothing is gathered around it (tp's
    # all-reduces after o_proj and the FFN are not gathers)
    assert "all-gather" not in program
    assert "all-to-all" not in program
