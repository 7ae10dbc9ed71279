"""Classifier backends on a mesh ≡ unsharded (the 8-device CPU emulation
of the reference's 'mpirun -np 8 on one box', SURVEY.md §4)."""

import jax
import numpy as np
import pytest

from music_analyst_tpu.parallel.mesh import MeshSpec, build_mesh
from music_analyst_tpu.serving.decode_loop import generate_batch_continuous

TEXTS = [
    "love and sunshine all day",
    "tears and pain in the lonely night",
    "",
    "la la la " * 40,
    "cry me a river of joy",
]


@pytest.fixture(scope="module")
def dp_mesh():
    return build_mesh(MeshSpec((("dp", 8),)))


@pytest.fixture(scope="module")
def dp_tp_mesh():
    return build_mesh(MeshSpec((("dp", 2), ("tp", 4))))


def test_distilbert_dp_sharded_matches_unsharded(dp_mesh):
    from music_analyst_tpu.models.distilbert import (
        DistilBertClassifier,
        DistilBertConfig,
    )

    cfg = DistilBertConfig.tiny()
    plain = DistilBertClassifier(config=cfg, max_len=64, seed=5)
    sharded = DistilBertClassifier(config=cfg, max_len=64, seed=5,
                                   mesh=dp_mesh)
    assert plain.classify_batch(TEXTS) == sharded.classify_batch(TEXTS)


def test_distilbert_dp_tp_sharded_matches_unsharded(dp_tp_mesh):
    from music_analyst_tpu.models.distilbert import (
        DistilBertClassifier,
        DistilBertConfig,
    )

    cfg = DistilBertConfig.tiny()
    plain = DistilBertClassifier(config=cfg, max_len=64, seed=6)
    sharded = DistilBertClassifier(config=cfg, max_len=64, seed=6,
                                   mesh=dp_tp_mesh)
    assert plain.classify_batch(TEXTS) == sharded.classify_batch(TEXTS)


def test_llama_tp_sharded_matches_unsharded(dp_tp_mesh):
    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )

    cfg = LlamaConfig(
        vocab_size=300, dim=32, n_layers=2, n_heads=4, n_kv_heads=4,
        hidden_dim=64, rope_theta=1e4, max_seq_len=128, dtype="float32",
    )
    plain = LlamaZeroShotClassifier(config=cfg, max_prompt_len=64, seed=7)
    sharded = LlamaZeroShotClassifier(config=cfg, max_prompt_len=64, seed=7,
                                      mesh=dp_tp_mesh)
    assert plain.classify_batch(TEXTS) == sharded.classify_batch(TEXTS)


# ---------------------------------------------- tensor-parallel decode
#
# float32 on purpose: the tp all-reduce changes float summation order,
# and in bf16 that flips greedy argmax on near-ties (PERFORMANCE.md
# "Scale-out serving").  In float32 at these widths the reduction is
# exact, so tp=N must be BYTE-identical to the single-chip runtimes.

GEN_PROMPTS = [
    "golden sunshine on the river",
    "rain",
    "shadows fall across the empty street tonight",
    "la la la la",
    "winter wind and summer fire",
    "the long road home winds past the silver lake",
]


def _gen_clf(mesh=None):
    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )

    cfg = LlamaConfig(
        vocab_size=512, dim=128, n_layers=2, n_heads=8, n_kv_heads=4,
        hidden_dim=256, rope_theta=1e4, max_seq_len=128, dtype="float32",
    )
    return LlamaZeroShotClassifier(config=cfg, max_prompt_len=64, seed=11,
                                   mesh=mesh)


@pytest.fixture(scope="module")
def plain_gen_clf():
    return _gen_clf()


@pytest.fixture(scope="module")
def tp2_gen_clf():
    mesh = build_mesh(MeshSpec((("tp", 2),)), devices=jax.devices()[:2])
    return _gen_clf(mesh=mesh)


def test_slot_decode_tp_byte_identical(plain_gen_clf, tp2_gen_clf):
    """tp=2 slot runtime emits byte-identical greedy text to tp=1
    (``page_size=0`` pins the monolithic slot cache)."""
    kwargs = dict(max_new_tokens=8, n_slots=4, prefill_chunk=16,
                  page_size=0)
    plain = generate_batch_continuous(plain_gen_clf, GEN_PROMPTS, **kwargs)
    tp = generate_batch_continuous(tp2_gen_clf, GEN_PROMPTS, **kwargs)
    assert tp == plain


def test_paged_decode_tp_byte_identical(plain_gen_clf, tp2_gen_clf):
    """tp=2 paged runtime (prefix sharing on, the serving default) is
    byte-identical to tp=1 paged and to the tp=1 slot route."""
    kwargs = dict(max_new_tokens=8, n_slots=4, prefill_chunk=16)
    plain = generate_batch_continuous(plain_gen_clf, GEN_PROMPTS, **kwargs)
    tp = generate_batch_continuous(tp2_gen_clf, GEN_PROMPTS, **kwargs)
    assert tp == plain


def test_tp4_decode_byte_identical(plain_gen_clf):
    """tp=4 shards one KV head per chip — the extreme split still
    matches single-chip exactly."""
    mesh = build_mesh(MeshSpec((("tp", 4),)), devices=jax.devices()[:4])
    tp4 = _gen_clf(mesh=mesh)
    kwargs = dict(max_new_tokens=6, n_slots=2, prefill_chunk=16)
    plain = generate_batch_continuous(plain_gen_clf, GEN_PROMPTS, **kwargs)
    assert generate_batch_continuous(tp4, GEN_PROMPTS, **kwargs) == plain


@pytest.mark.parametrize("page_size", [0, None])
def test_tp_decode_zero_retraces(tp2_gen_clf, page_size):
    """The fixed-program discipline survives the mesh: after warmup a
    mixed-length tp workload compiles nothing new (slot and paged)."""
    from music_analyst_tpu.serving.decode_loop import ContinuousScheduler

    sched = ContinuousScheduler(
        tp2_gen_clf, n_slots=4, prefill_chunk=16, prompt_region=64,
        max_new_tokens=8, max_queue=64, page_size=page_size,
    )
    sched.warmup()
    before = sched.runtime.compiled_variants()
    prompts = [GEN_PROMPTS[i % len(GEN_PROMPTS)] for i in range(10)]
    reqs = [
        sched.submit(i, p, max_new_tokens=1 + i % 7)
        for i, p in enumerate(prompts)
    ]
    sched.run_until_idle()
    assert all(r.response and r.response.get("ok") for r in reqs)
    assert sched.runtime.compiled_variants() == before


def test_tp_runtime_kv_cache_is_head_sharded(tp2_gen_clf):
    """The slot cache's head axis actually lands on the tp axis (not
    silently replicated): 4 kv heads over tp=2."""
    from jax.sharding import PartitionSpec as P

    from music_analyst_tpu.serving.decode_runtime import slot_runtime

    rt = slot_runtime(tp2_gen_clf, n_slots=2, prefill_chunk=16,
                      max_new_tokens=4, prompt_region=32)
    caches = rt.init_caches()
    spec = caches[0].keys.sharding.spec
    assert tuple(spec) == (None, None, "tp", None)
    assert caches[0].length.sharding.is_fully_replicated


def test_kv_cache_spec_degrades_to_replicated(dp_mesh, dp_tp_mesh):
    """tp absent, or a tp width the head count can't split, falls back
    to the replicated single-chip layout instead of failing placement."""
    from jax.sharding import PartitionSpec as P

    from music_analyst_tpu.parallel.sharding import kv_cache_spec

    kv, lens = kv_cache_spec(dp_tp_mesh, n_kv_heads=4)  # tp=4 | 4 heads
    assert kv == P(None, None, "tp", None) and lens == P()
    kv, _ = kv_cache_spec(dp_tp_mesh, n_kv_heads=3)  # 4 ∤ 3 → replicate
    assert kv == P()
    kv, _ = kv_cache_spec(dp_mesh, n_kv_heads=4)  # no tp axis at all
    assert kv == P()


def test_serve_mesh_resolves_and_validates(monkeypatch):
    from music_analyst_tpu.serving.server import serve_mesh

    assert serve_mesh(None) is None
    assert serve_mesh(1) is None
    mesh = serve_mesh(2)
    assert mesh.axis_names == ("tp",) and mesh.devices.size == 2
    with pytest.raises(ValueError):
        serve_mesh(64)  # more chips than the host has
    monkeypatch.setenv("MUSICAAL_SERVE_TP", "4")
    assert serve_mesh(None).devices.size == 4


def test_sentiment_engine_with_mesh_backend(dp_mesh, tmp_path):
    """run_sentiment over a mesh-backed classifier produces the standard
    artifacts with all songs accounted for."""
    from music_analyst_tpu.engines.sentiment import run_sentiment
    from music_analyst_tpu.models.distilbert import (
        DistilBertClassifier,
        DistilBertConfig,
    )

    backend = DistilBertClassifier(
        config=DistilBertConfig.tiny(), max_len=64, mesh=dp_mesh
    )
    import os

    fixture = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "fixtures",
        "mini_songs.csv",
    )
    result = run_sentiment(
        fixture, backend=backend, batch_size=3,
        output_dir=str(tmp_path), quiet=True,
    )
    assert sum(result.counts.values()) == len(result.rows) == 8
