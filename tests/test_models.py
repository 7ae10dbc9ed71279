"""Model families: shapes, KV-cache consistency, TP-sharded equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from music_analyst_tpu.models.distilbert import (
    DistilBertClassifier,
    DistilBertConfig,
)
from music_analyst_tpu.models.llama import (
    LlamaConfig,
    LlamaModel,
    LlamaZeroShotClassifier,
    init_caches,
)
from music_analyst_tpu.models.layers import causal_mask, padding_mask
from music_analyst_tpu.parallel.mesh import build_mesh, factor_devices
from music_analyst_tpu.parallel.sharding import shard_params
from music_analyst_tpu.utils.labels import SUPPORTED_LABELS


class TestDistilBert:
    @pytest.fixture(scope="class")
    def clf(self):
        return DistilBertClassifier(
            config=DistilBertConfig.tiny(), max_len=32
        )

    def test_forward_shapes(self, clf):
        ids = jnp.zeros((3, 32), jnp.int32)
        lens = jnp.array([5, 1, 32], jnp.int32)
        logits = clf.model.apply({"params": clf.params}, ids, lens)
        assert logits.shape == (3, 2)
        assert logits.dtype == jnp.float32

    def test_padding_invariance(self, clf):
        """Garbage in padded positions must not change the prediction."""
        rng = np.random.default_rng(0)
        ids_a = np.zeros((1, 32), np.int32)
        ids_a[0, :6] = [101, 7, 8, 9, 10, 102]
        ids_b = ids_a.copy()
        ids_b[0, 6:] = rng.integers(1, 1000, 26)
        lens = jnp.array([6], jnp.int32)
        la = clf.model.apply({"params": clf.params}, jnp.asarray(ids_a), lens)
        lb = clf.model.apply({"params": clf.params}, jnp.asarray(ids_b), lens)
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=2e-2)

    def test_classify_batch_contract(self, clf):
        labels = clf.classify_batch(["i love this", "", "terrible pain"])
        assert all(l in SUPPORTED_LABELS for l in labels)
        assert labels[1] == "Neutral"  # empty lyric rule

    def test_int16_wire_ids_lossless(self):
        """Token ids ship int16 (vocab fits) and widen on device; labels
        must match a forced-int32 wire exactly."""
        import numpy as np

        clf = DistilBertClassifier(config=DistilBertConfig.tiny(), max_len=32)
        assert clf._wire_dtype == np.int16
        texts = ["la la love", "pain and tears tonight", ""]
        got = clf.classify_batch(texts)
        clf._wire_dtype = np.int32
        assert clf.classify_batch(texts) == got

    def test_neutral_threshold_extremes(self):
        clf = DistilBertClassifier(
            config=DistilBertConfig.tiny(), max_len=16, neutral_threshold=1.1
        )
        # threshold > 1 -> everything Neutral
        assert clf.classify_batch(["anything at all"]) == ["Neutral"]


class TestDistilBertLengthBuckets:
    """Bucketed inference: same labels, shorter compiled sequences."""

    def _mixed_texts(self):
        return [
            "short",
            "",
            "a medium length lyric with a handful of words in it",
            "long " + "word " * 60,
            "tiny one",
            "another long lyric " + "la la love rain " * 20,
        ]

    def test_matches_unbucketed_float32(self):
        """In float32 the bucketed path is numerically the unbucketed path
        (padding invariance), so labels must agree exactly."""
        import dataclasses

        cfg = dataclasses.replace(DistilBertConfig.tiny(), dtype="float32")
        plain = DistilBertClassifier(config=cfg, max_len=64, seed=5)
        bucketed = DistilBertClassifier(
            config=cfg, max_len=64, seed=5, length_buckets=(16, 32)
        )
        bucketed.params = plain.params
        texts = self._mixed_texts() * 3
        assert bucketed.classify_batch(texts) == plain.classify_batch(texts)

    def test_routing_and_order_restoration(self):
        """Every row routes to the smallest sufficient bucket and comes
        back in input order (deterministic fake forward)."""
        clf = DistilBertClassifier(
            config=DistilBertConfig.tiny(), max_len=64,
            length_buckets=(16, 32), neutral_threshold=0.5,
        )
        seen_seqs = []

        def fake_forward(params, token_ids, lengths):
            seen_seqs.append(token_ids.shape[1])
            # class = row length parity; confidence = certain
            return np.asarray(lengths) % 2, np.ones(lengths.shape[0])

        clf._forward = fake_forward
        texts = self._mixed_texts()
        _, lengths = clf.tokenizer.encode_batch(texts, clf.max_len)
        labels = clf.classify_batch(texts)
        want = [
            "Neutral" if not t.strip()
            else clf._CLASS_LABELS[int(n) % 2]
            for t, n in zip(texts, lengths)
        ]
        assert labels == want
        assert set(seen_seqs) <= {16, 32, 64}
        assert len(seen_seqs) >= 2  # mixed lengths hit multiple buckets

    def test_single_bucket_when_all_short(self):
        clf = DistilBertClassifier(
            config=DistilBertConfig.tiny(), max_len=64, length_buckets=(16,)
        )
        seen = []
        real = clf._forward
        clf._forward = lambda p, i, l: (seen.append(i.shape), real(p, i, l))[1]
        clf.classify_batch(["hi there", "la la", "ok"])
        assert all(shape[1] == 16 for shape in seen)
        # rows round up to the power-of-two floor
        assert all(shape[0] == 16 for shape in seen)

    def test_bucketed_on_dp_mesh(self):
        mesh = build_mesh(factor_devices(8, ("dp",)))
        clf = DistilBertClassifier(
            config=DistilBertConfig.tiny(), max_len=64, mesh=mesh,
            length_buckets=(16, 32),
        )
        labels = clf.classify_batch(self._mixed_texts())
        assert len(labels) == 6
        assert all(l in SUPPORTED_LABELS for l in labels)
        assert labels[1] == "Neutral"

    def test_bucket_validation(self):
        with pytest.raises(ValueError, match="floor"):
            DistilBertClassifier(
                config=DistilBertConfig.tiny(), max_len=64, length_buckets=(4,)
            )
        with pytest.raises(ValueError, match="exceeds max_len"):
            DistilBertClassifier(
                config=DistilBertConfig.tiny(), max_len=64,
                length_buckets=(128,),
            )

    def test_derive_length_buckets(self):
        from music_analyst_tpu.models.distilbert import derive_length_buckets

        # Cap-dominated corpus (the headline shape): no bucket is worth a
        # compiled program, flat path stays.
        assert derive_length_buckets(np.full(100, 128), 128) == ()
        # Short-skewed corpus: real buckets come back, ascending.
        short = np.concatenate([np.full(40, 20), np.full(40, 50),
                                np.full(20, 128)])
        assert derive_length_buckets(short, 128) == (32, 64)
        # Rows of a dropped bucket roll upward into the next kept one.
        mixed = np.concatenate([np.full(3, 10), np.full(47, 30),
                                np.full(50, 128)])
        assert derive_length_buckets(mixed, 128) == (32,)
        # Degenerate inputs.
        assert derive_length_buckets(np.array([]), 128) == ()
        assert derive_length_buckets(np.full(10, 4), 16) == ()

    def test_auto_buckets_resolve_on_first_batch(self):
        clf = DistilBertClassifier(
            config=DistilBertConfig.tiny(), max_len=64, length_buckets="auto"
        )
        assert clf.length_buckets == "auto"
        labels = clf.classify_batch(["hi there you", "la la love"] * 20)
        assert len(labels) == 40
        # All-short corpus → a real short bucket was derived (plus the
        # implicit max_len bucket _check_buckets appends).
        assert isinstance(clf.length_buckets, tuple)
        assert clf.length_buckets[0] < 64
        # Second batch reuses the resolved buckets (no re-derivation).
        resolved = clf.length_buckets
        clf.classify_batch(["longer lyric " + "word " * 60])
        assert clf.length_buckets is resolved

    def test_auto_buckets_pend_through_empty_batches(self):
        """An empty first batch must not resolve auto to the flat path."""
        clf = DistilBertClassifier(
            config=DistilBertConfig.tiny(), max_len=64, length_buckets="auto"
        )
        assert clf.classify_batch([]) == []
        assert clf.length_buckets == "auto"  # still pending
        clf.classify_batch(["short words"] * 4)
        assert isinstance(clf.length_buckets, tuple)

    def test_auto_buckets_stay_flat_on_capped_corpus(self):
        clf = DistilBertClassifier(
            config=DistilBertConfig.tiny(), max_len=64, length_buckets="auto"
        )
        long_texts = ["word " * 100] * 8
        clf.classify_batch(long_texts)
        assert clf.length_buckets is None


class TestLlama:
    @pytest.fixture(scope="class")
    def clf(self):
        return LlamaZeroShotClassifier(
            config=LlamaConfig.tiny(), max_prompt_len=160
        )

    def test_prefill_matches_no_cache(self, clf):
        """Prefill-with-cache logits == plain forward logits."""
        cfg = clf.config
        B, S = 2, 12
        ids = jnp.asarray(
            np.random.default_rng(1).integers(0, 256, (B, S)), jnp.int32
        )
        pos = jnp.arange(S)[None, :].repeat(B, 0)
        mask = causal_mask(S, S, 0)
        plain, _ = clf.model.apply({"params": clf.params}, ids, pos, mask)
        caches = init_caches(cfg, B, S + 4)
        mask_c = causal_mask(S, S + 4, 0)
        cached, caches = clf.model.apply(
            {"params": clf.params}, ids, pos, mask_c, caches
        )
        np.testing.assert_allclose(
            np.asarray(plain), np.asarray(cached), rtol=2e-2, atol=2e-2
        )

    def test_incremental_decode_matches_full_forward(self, clf):
        """Token-by-token decode reproduces the full-sequence argmax path."""
        cfg = clf.config
        rng = np.random.default_rng(2)
        S = 10
        ids = jnp.asarray(rng.integers(0, 256, (1, S)), jnp.int32)
        pos = jnp.arange(S)[None, :]
        full_logits, _ = clf.model.apply(
            {"params": clf.params}, ids, pos, causal_mask(S, S, 0)
        )
        # incremental: prefill first 5, then decode 5 one at a time
        caches = init_caches(cfg, 1, S)
        pre = 5
        logits_p, caches = clf.model.apply(
            {"params": clf.params},
            ids[:, :pre],
            pos[:, :pre],
            causal_mask(pre, S, 0),
            caches,
        )
        step_logits = [logits_p[:, -1]]
        for t in range(pre, S):
            kv_pos = jnp.arange(S)[None, None, None, :]
            mask = kv_pos <= t
            logits_t, caches = clf.model.apply(
                {"params": clf.params},
                ids[:, t : t + 1],
                pos[:, t : t + 1],
                mask,
                caches,
            )
            step_logits.append(logits_t[:, -1])
        for t in range(pre, S):
            np.testing.assert_allclose(
                np.asarray(full_logits[:, t - 1]),
                np.asarray(step_logits[t - pre]),
                rtol=5e-2,
                atol=5e-2,
            )

    def test_classify_batch_contract(self, clf):
        labels = clf.classify_batch(["love and joy", "", "tears of pain"])
        assert all(l in SUPPORTED_LABELS for l in labels)
        assert labels[1] == "Neutral"

    def test_generation_path(self, clf):
        text = clf.generate("hello", max_new_tokens=4)
        assert isinstance(text, str)
        label = clf.classify_by_generation("some lyrics here")
        assert label in SUPPORTED_LABELS

    def test_preset_llama3_requires_checkpoint(self):
        with pytest.raises(RuntimeError, match="checkpoint"):
            LlamaZeroShotClassifier.from_pretrained_or_random("llama3")


class TestTensorParallel:
    def test_sharded_forward_matches_single_device(self):
        """dp×tp sharded forward == unsharded forward (same params)."""
        cfg = LlamaConfig.tiny()
        model = LlamaModel(cfg)
        rng = np.random.default_rng(3)
        ids = jnp.asarray(rng.integers(0, 256, (4, 16)), jnp.int32)
        pos = jnp.arange(16)[None, :].repeat(4, 0)
        mask = causal_mask(16, 16, 0)
        params = model.init(jax.random.key(0), ids, pos, mask)["params"]
        ref_logits, _ = model.apply({"params": params}, ids, pos, mask)

        mesh = build_mesh(factor_devices(8, ("dp", "tp"), fixed={"tp": 4}))
        sharded = shard_params(params, mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P

        ids_s = jax.device_put(ids, NamedSharding(mesh, P("dp")))
        pos_s = jax.device_put(pos, NamedSharding(mesh, P("dp")))
        out, _ = jax.jit(
            lambda p, i, q: model.apply({"params": p}, i, q, mask)
        )(sharded, ids_s, pos_s)
        ref_np, out_np = np.asarray(ref_logits), np.asarray(out)
        # bf16 all-reduce ordering differs across shards; demand near-total
        # elementwise agreement plus identical argmax decisions wherever
        # the decision isn't a near-tie (a reduction-order flip can
        # legitimately swap a top-2 pair separated by less than bf16
        # noise — the typical margin on this corpus is ~0.24).
        close = np.isclose(ref_np, out_np, rtol=3e-2, atol=3e-2)
        assert close.mean() > 0.999
        agree = ref_np.argmax(-1) == out_np.argmax(-1)
        srt = np.sort(ref_np, axis=-1)
        margin = srt[..., -1] - srt[..., -2]
        assert agree[margin > 0.02].all(), margin[~agree]
        assert agree.mean() > 0.95

    def test_partition_specs_cover_attention_and_mlp(self):
        cfg = LlamaConfig.tiny()
        model = LlamaModel(cfg)
        ids = jnp.zeros((1, 8), jnp.int32)
        pos = jnp.zeros((1, 8), jnp.int32)
        params = model.init(jax.random.key(0), ids, pos, causal_mask(8, 8, 0))[
            "params"
        ]
        from music_analyst_tpu.parallel.sharding import partition_specs
        from jax.sharding import PartitionSpec as P

        specs = partition_specs(params)
        l0 = specs["layer_0"]
        assert l0["attention"]["q_proj"]["kernel"] == P(None, "tp", None)
        assert l0["attention"]["o_proj"]["kernel"] == P("tp", None, None)
        assert l0["feed_forward"]["gate_proj"]["kernel"] == P(None, "tp")
        assert l0["feed_forward"]["down_proj"]["kernel"] == P("tp", None)
        assert specs["tok_embeddings"]["embedding"] == P("tp", None)
        assert specs["lm_head"]["kernel"] == P(None, "tp")
        assert specs["norm"]["scale"] == P()


def test_generate_scan_matches_step_loop():
    """Single-jit scan generation ≡ the explicit per-token step loop."""
    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )

    cfg = LlamaConfig(
        vocab_size=300, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        hidden_dim=64, rope_theta=1e4, max_seq_len=128, dtype="float32",
    )
    clf = LlamaZeroShotClassifier(config=cfg, max_prompt_len=32, seed=3)
    prompts = ["hello world", "la la la la la la", "x"]
    batched = clf.generate_batch(prompts, max_new_tokens=8)
    singles = [clf.generate(p, max_new_tokens=8) for p in prompts]
    assert batched == singles


def test_classify_batch_by_generation():
    """classify_batch_by_generation classifies via batched free-text decode
    + the shared normalizer, honoring the empty-lyric rule."""
    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )

    cfg = LlamaConfig(
        vocab_size=300, dim=32, n_layers=1, n_heads=4, n_kv_heads=2,
        hidden_dim=64, rope_theta=1e4, max_seq_len=128, dtype="float32",
    )
    clf = LlamaZeroShotClassifier(config=cfg, max_prompt_len=32)
    labels = clf.classify_batch_by_generation(["some lyrics", ""])
    assert labels[1] == "Neutral"
    assert all(l in ("Positive", "Neutral", "Negative") for l in labels)
    singles = [clf.classify_by_generation("some lyrics")]
    assert labels[0] == singles[0]  # batch ≡ single-song reference path


class TestLlamaPromptTrimming:
    """Prefill pads to a power-of-two over the batch's longest prompt,
    not to max_prompt_len (the decoder analogue of length buckets).

    The equality tests compare programs compiled at different widths.
    Masked padding contributes exact zeros, but XLA may reassociate the
    non-zero accumulations differently per shape, so equality is a
    last-ulp assumption: exact on the CI platform (CPU, fixed seed,
    float32 config), not a cross-platform guarantee.  A flake here on new
    hardware means a near-tied argmax, not a trimming bug.
    """

    def _clf(self, **kw):
        from music_analyst_tpu.models.llama import (
            LlamaConfig,
            LlamaZeroShotClassifier,
        )

        cfg = LlamaConfig(
            vocab_size=300, dim=32, n_layers=1, n_heads=4, n_kv_heads=2,
            hidden_dim=64, rope_theta=1e4, max_seq_len=1024, dtype="float32",
        )
        return LlamaZeroShotClassifier(
            config=cfg, max_prompt_len=512, **kw
        )

    def test_short_batch_scores_at_trimmed_width(self):
        clf = self._clf()
        seen = []
        real = clf._score_labels
        clf._score_labels = lambda p, ids, lens, li, ll, **static: (
            seen.append(ids.shape), real(p, ids, lens, li, ll, **static)
        )[1]
        clf.classify_batch(["la la", "short one"])
        assert seen and seen[0][1] < 512
        # width is a power of two >= the longest prompt
        assert seen[0][1] & (seen[0][1] - 1) == 0

    def test_trimming_preserves_labels(self):
        clf = self._clf()
        texts = ["short", "mid length lyric with several words " * 2,
                 "long " + "word " * 150, ""]
        trimmed = clf.classify_batch(texts)
        clf._trim_prompt_pad = lambda ids, lens: (ids, lens)  # disable
        flat = clf.classify_batch(texts)
        assert trimmed == flat

    def test_trimming_preserves_generations(self):
        clf = self._clf()
        prompts = ["say something nice", "la"]
        trimmed = clf.generate_batch(prompts, max_new_tokens=8)
        clf._trim_prompt_pad = lambda ids, lens: (ids, lens)
        flat = clf.generate_batch(prompts, max_new_tokens=8)
        assert trimmed == flat

    def test_long_prompt_not_cut(self):
        clf = self._clf()
        ids, lens = clf._encode_prompts(["word " * 600])  # > 512 tokens
        assert ids.shape[1] == 512
        assert int(lens[0]) == 512
