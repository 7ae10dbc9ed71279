"""Fused paged-attention decode kernel (ops/paged_attention.py).

Three layers of coverage for the ISSUE 18 tentpole:

* kernel vs. oracle — both Pallas bodies (exact batched and the
  page-streaming online-softmax TPU body, run under interpret) against
  the naive f32 reference, across page sizes {8, 16}, odd valid
  lengths, and table rows parked on the trash page;
* bitwise contract — the exact body must reproduce
  ``models/layers.dot_product_attention`` over the gathered view BIT FOR
  BIT (a 1-ulp logit difference flips greedy argmax near-ties, which is
  how the paged scheduler's byte-identity guarantee would silently rot);
* int8 KV — per-(page, row) symmetric quantization round-trips, bounds
  its error, survives the sharded end-to-end path with matching labels,
  reports its pool-byte savings, and degrades byte-identically when the
  ``kv_quant.dequant`` fault site fires.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from music_analyst_tpu.models.layers import dot_product_attention
from music_analyst_tpu.ops.paged_attention import (
    PagedAttnView,
    paged_attention,
    paged_attention_reference,
)
from music_analyst_tpu.ops.quant import dequantize_kv_page, quantize_kv_page
from music_analyst_tpu.serving.batcher import resolve_kv_quant
from music_analyst_tpu.utils.labels import normalise_label


# ---------------------------------------------------------------------------
# Random paged state
# ---------------------------------------------------------------------------


def _random_case(seed, page_size, *, n=3, H=4, n_kv=2, D=8, pps=4,
                 total=None, trash_garbage=0.0, quantized=False):
    """Random pool/table/mask with odd per-slot lengths and trash rows.

    Slot 0's final table entry points at the trash page (its valid
    length keeps it fully masked), mirroring a slot whose budget never
    reaches its last decode page.  ``trash_garbage`` fills the trash
    page with that constant so isolation is observable.
    """
    rng = np.random.RandomState(seed)
    P = page_size
    span = pps * P
    total = span if total is None else total
    n_pages = n * pps
    table = rng.permutation(n_pages).reshape(n, pps).astype(np.int32)
    table[0, -1] = n_pages  # trash page
    # Odd lengths, capped so slot 0 never reads its trash-backed page.
    lengths = np.array(
        [rng.randint(0, min(total, span - P) // 2) * 2 + 1
         for _ in range(n)],
        dtype=np.int32,
    )
    mask = np.arange(total)[None, :] < lengths[:, None]
    kv_shape = (n_pages + 1, P, n_kv, D)
    keys = rng.standard_normal(kv_shape).astype(np.float32)
    values = rng.standard_normal(kv_shape).astype(np.float32)
    keys[n_pages] = trash_garbage
    values[n_pages] = trash_garbage
    q = jnp.asarray(
        rng.standard_normal((n, 1, H, D)), dtype=jnp.bfloat16
    )
    if quantized:
        kq, ks = quantize_kv_page(jnp.asarray(keys))
        vq, vs = quantize_kv_page(jnp.asarray(values))
        pools = dict(key_scale=ks, value_scale=vs)
        kp, vp = kq, vq
    else:
        pools = {}
        kp = jnp.asarray(keys, dtype=jnp.bfloat16)
        vp = jnp.asarray(values, dtype=jnp.bfloat16)
    return dict(
        q=q, key_pages=kp, value_pages=vp,
        table=jnp.asarray(table), mask=jnp.asarray(mask),
        lengths=lengths, f32_keys=keys, f32_values=values, **pools,
    )


def _call(case, **kw):
    return paged_attention(
        case["q"], case["key_pages"], case["value_pages"],
        case["table"], case["mask"],
        key_scale=case.get("key_scale"),
        value_scale=case.get("value_scale"),
        interpret=True, **kw,
    )


def _oracle(case):
    return np.asarray(paged_attention_reference(
        case["q"], case["key_pages"], case["value_pages"],
        case["table"], case["mask"],
        key_scale=case.get("key_scale"),
        value_scale=case.get("value_scale"),
    ))


# ---------------------------------------------------------------------------
# Kernel vs. oracle (both bodies, both page sizes, odd lengths, trash rows)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("page_size", [8, 16])
@pytest.mark.parametrize("stream", [False, True])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_kernel_matches_oracle(page_size, stream, seed):
    """Seeded property sweep: fused kernel ≈ naive f32 gather oracle.

    ``total`` deliberately lands off the page grid on odd seeds so the
    exact body's ``[:, :total]`` slice and the streaming body's padded
    mask tail both get exercised.
    """
    total = None if seed % 2 == 0 else page_size * 4 - 5
    case = _random_case(seed, page_size, total=total)
    out = np.asarray(_call(case, stream=stream), dtype=np.float32)
    ref = _oracle(case)
    assert out.shape == ref.shape
    np.testing.assert_allclose(out, ref, atol=0.06, rtol=0.06)


def test_kernel_matches_oracle_hypothesis():
    """Hypothesis variant of the sweep (skips when hypothesis is not
    installed — the seeded sweep above always runs)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(
        seed=st.integers(0, 2 ** 16),
        page_size=st.sampled_from([8, 16]),
        stream=st.booleans(),
    )
    def _property(seed, page_size, stream):
        case = _random_case(seed, page_size)
        out = np.asarray(_call(case, stream=stream), dtype=np.float32)
        np.testing.assert_allclose(out, _oracle(case), atol=0.06, rtol=0.06)

    _property()


@pytest.mark.parametrize("page_size", [8, 16])
def test_exact_body_bitwise_vs_dense(page_size):
    """The exact body IS dense attention over the gathered view, bitwise.

    The decode scan's byte-identity to the monolithic runtime rests on
    this: the kernel may not reassociate a single multiply-add relative
    to ``dot_product_attention`` (a 1-ulp logit drift flips greedy
    argmax near-ties — observed live during ISSUE 18 bring-up when a
    grouped no-repeat einsum replaced the repeat broadcast).
    """
    for seed in range(4):
        case = _random_case(seed, page_size)
        out = np.asarray(_call(case, stream=False))
        n, pps = case["table"].shape
        view = lambda pool: jnp.take(pool, case["table"], axis=0).reshape(
            n, pps * page_size, *pool.shape[2:]
        )
        dense = np.asarray(dot_product_attention(
            case["q"], view(case["key_pages"]), view(case["value_pages"]),
            case["mask"][:, None, None, :],
        ))
        assert out.tobytes() == dense.tobytes()


def test_trash_page_contents_never_leak():
    """Garbage in the trash page (dangling writes from freed slots) must
    not perturb any output lane, in either body."""
    for stream in (False, True):
        clean = _random_case(7, 8, trash_garbage=0.0)
        dirty = _random_case(7, 8, trash_garbage=7777.0)
        a = np.asarray(_call(clean, stream=stream))
        b = np.asarray(_call(dirty, stream=stream))
        assert a.tobytes() == b.tobytes()


def test_geometry_validation():
    case = _random_case(0, 8)
    with pytest.raises(ValueError, match="decode kernel"):
        paged_attention(
            jnp.zeros((3, 2, 4, 8), jnp.bfloat16), case["key_pages"],
            case["value_pages"], case["table"], case["mask"],
            interpret=True,
        )
    with pytest.raises(ValueError, match="passed together"):
        paged_attention(
            case["q"], case["key_pages"], case["value_pages"],
            case["table"], case["mask"],
            key_scale=jnp.ones((25, 8)), interpret=True,
        )


# ---------------------------------------------------------------------------
# PagedAttnView: the KVCache-shaped adapter the decode scan carries
# ---------------------------------------------------------------------------


def test_view_update_lands_in_physical_page():
    case = _random_case(3, 8)
    n = case["table"].shape[0]
    lengths = jnp.asarray(case["lengths"])
    view = PagedAttnView(
        keys=case["key_pages"], values=case["value_pages"],
        key_scale=None, value_scale=None,
        table=case["table"], length=lengths,
        page_size=8, total=case["mask"].shape[-1],
    )
    k_new = jnp.asarray(
        np.random.RandomState(9).standard_normal((n, 1, 2, 8)),
        dtype=jnp.bfloat16,
    )
    new = view.update(k_new, k_new * 2)
    assert np.array_equal(np.asarray(new.length), case["lengths"] + 1)
    table = np.asarray(case["table"])
    for s in range(n):
        off = int(case["lengths"][s])
        phys, r = table[s, off // 8], off % 8
        got = np.asarray(new.keys[phys, r])
        assert got.tobytes() == np.asarray(k_new[s, 0]).tobytes()
    # attend == the plain kernel call on the same state.
    mask = jnp.arange(view.total)[None, :] < (lengths + 1)[:, None]
    out = np.asarray(new.attend(case["q"], mask[:, None, None, :]))
    direct = np.asarray(paged_attention(
        case["q"], new.keys, new.values, new.table, mask, interpret=True,
    ))
    assert out.tobytes() == direct.tobytes()


def test_view_rejects_chunked_writes():
    case = _random_case(0, 8)
    view = PagedAttnView(
        keys=case["key_pages"], values=case["value_pages"],
        key_scale=None, value_scale=None,
        table=case["table"], length=jnp.zeros(3, jnp.int32),
        page_size=8, total=32,
    )
    with pytest.raises(ValueError, match="one decode token"):
        view.update(jnp.zeros((3, 4, 2, 8)), jnp.zeros((3, 4, 2, 8)))


# ---------------------------------------------------------------------------
# int8 KV pages
# ---------------------------------------------------------------------------


def test_quantize_roundtrip_contract():
    """The paged prefill re-scatters boundary pages, so round-trip drift
    must not compound: exact through f32, ≤ ±1 code through the bf16
    compute dtype, and the bf16 round-trip is a fixed point after one
    pass (rescattering the same page again changes nothing)."""
    for seed in (3, 11, 19):
        rng = np.random.RandomState(seed)
        x = jnp.asarray(
            rng.standard_normal((16, 2, 8)) * rng.uniform(0.1, 10),
            dtype=jnp.float32,
        )
        codes, scale = quantize_kv_page(x)
        exact, scale2 = quantize_kv_page(
            dequantize_kv_page(codes, scale, jnp.float32)
        )
        assert np.array_equal(np.asarray(codes), np.asarray(exact))
        np.testing.assert_allclose(
            np.asarray(scale2), np.asarray(scale), rtol=1e-2
        )
        once, s_once = quantize_kv_page(
            dequantize_kv_page(codes, scale, jnp.bfloat16)
        )
        drift = np.abs(
            np.asarray(once, np.int32) - np.asarray(codes, np.int32)
        )
        assert drift.max() <= 1
        twice, _ = quantize_kv_page(
            dequantize_kv_page(once, s_once, jnp.bfloat16)
        )
        assert np.array_equal(np.asarray(once), np.asarray(twice))


@pytest.mark.parametrize("stream", [False, True])
@pytest.mark.parametrize("page_size", [8, 16])
def test_int8_kernel_bounded_error(page_size, stream):
    """int8 path: tight against the int8 oracle (same codes, same
    dequant), bounded against the unquantized f32 truth."""
    for seed in range(3):
        case = _random_case(seed, page_size, quantized=True)
        out = np.asarray(_call(case, stream=stream), dtype=np.float32)
        np.testing.assert_allclose(out, _oracle(case), atol=0.06, rtol=0.06)
        exact = dict(case)
        exact.pop("key_scale"), exact.pop("value_scale")
        exact["key_pages"] = jnp.asarray(case["f32_keys"])
        exact["value_pages"] = jnp.asarray(case["f32_values"])
        err = np.abs(out - _oracle(exact))
        assert err.max() < 0.15
        assert err.mean() < 0.03


def test_int8_view_update_quantizes_row():
    case = _random_case(5, 8, quantized=True)
    n = case["table"].shape[0]
    lengths = jnp.asarray(case["lengths"])
    view = PagedAttnView(
        keys=case["key_pages"], values=case["value_pages"],
        key_scale=case["key_scale"], value_scale=case["value_scale"],
        table=case["table"], length=lengths,
        page_size=8, total=case["mask"].shape[-1],
    )
    k_new = jnp.asarray(
        np.random.RandomState(4).standard_normal((n, 1, 2, 8)),
        dtype=jnp.bfloat16,
    )
    new = view.update(k_new, k_new)
    table = np.asarray(case["table"])
    want_codes, want_scale = quantize_kv_page(k_new[:, 0])
    for s in range(n):
        off = int(case["lengths"][s])
        phys, r = table[s, off // 8], off % 8
        assert np.array_equal(
            np.asarray(new.keys[phys, r]), np.asarray(want_codes[s])
        )
        assert float(new.key_scale[phys, r]) == pytest.approx(
            float(want_scale[s])
        )


# ---------------------------------------------------------------------------
# Serving integration: knob, warmup, stats, sharded labels, chaos degrade
# ---------------------------------------------------------------------------

PROMPTS = [
    "golden sunshine over the river",
    "broken hearts mend slowly tonight",
    "dancing alone under silver skies",
    "thunder rolls across the mountain",
    "whisper my name in the morning",
    "yesterday is gone forever now",
]


@pytest.fixture(scope="module")
def clf():
    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )

    return LlamaZeroShotClassifier(
        config=LlamaConfig.tiny(), max_prompt_len=64
    )


def _scheduler(clf, **kwargs):
    from music_analyst_tpu.serving.decode_loop import ContinuousScheduler

    kwargs.setdefault("n_slots", 4)
    kwargs.setdefault("prefill_chunk", 16)
    kwargs.setdefault("prompt_region", 64)
    kwargs.setdefault("max_new_tokens", 8)
    return ContinuousScheduler(clf, **kwargs)


def _run(sched, prompts, budget=8):
    reqs = [
        sched.submit(i, p, max_new_tokens=budget)
        for i, p in enumerate(prompts)
    ]
    sched.run_until_idle()
    out = []
    for req in reqs:
        resp = req.response or {}
        assert resp.get("ok"), resp
        out.append(resp["text"])
    return out


def test_resolve_kv_quant_knob(monkeypatch):
    monkeypatch.delenv("MUSICAAL_SERVE_KV_QUANT", raising=False)
    assert resolve_kv_quant(None) == "none"
    assert resolve_kv_quant("int8") == "int8"
    monkeypatch.setenv("MUSICAAL_SERVE_KV_QUANT", "INT8")
    assert resolve_kv_quant(None) == "int8"
    assert resolve_kv_quant("none") == "none"  # explicit beats env
    monkeypatch.setenv("MUSICAAL_SERVE_KV_QUANT", "fp4")
    assert resolve_kv_quant(None) == "none"  # malformed env falls back
    with pytest.raises(ValueError, match="kv_quant"):
        resolve_kv_quant("fp4")  # explicit malformed raises


def test_kv_quant_requires_paged_backend(clf):
    with pytest.raises(ValueError, match="paged"):
        _scheduler(clf, page_size=0, kv_quant="int8")


def test_int8_scheduler_end_to_end(clf):
    """int8 pool: same labels as the unquantized scheduler, warmup stays
    at the pinned 4 programs, and the stats block reports the ≥1.8×
    pool-byte savings the manifest advertises."""
    plain = _run(_scheduler(clf, kv_quant="none"), PROMPTS)
    sched = _scheduler(clf, kv_quant="int8")
    record = sched.warmup()
    assert record["programs"] == 4
    assert record["kv_quant"] == "int8"
    texts = _run(sched, PROMPTS)
    labels = [normalise_label(t) for t in texts]
    want = [normalise_label(t) for t in plain]
    agreement = np.mean([a == b for a, b in zip(labels, want)])
    assert agreement >= 0.98
    kq = sched.stats()["kv_quant"]
    assert kq["scheme"] == "int8" and kq["degraded"] is False
    assert kq["compression"] >= 1.8
    assert kq["pool_bytes"] * 1.8 <= kq["pool_bytes_unquantized"]
    assert kq["bytes_saved"] == (
        kq["pool_bytes_unquantized"] - kq["pool_bytes"]
    )
    assert kq["hbm_bytes_per_seq"] * 1.8 <= (
        kq["hbm_bytes_per_seq_unquantized"]
    )


def test_int8_sharded_label_agreement():
    """End-to-end on the sharded mesh (dp×tp): int8 labels agree ≥ 0.98
    with the unquantized run, with speculation composed on top."""
    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )
    from music_analyst_tpu.parallel.mesh import build_mesh, factor_devices
    from music_analyst_tpu.serving.decode_loop import (
        generate_batch_continuous,
    )

    mesh = build_mesh(factor_devices(8, ("dp", "tp"), fixed={"tp": 2}))
    clf = LlamaZeroShotClassifier(
        config=LlamaConfig.tiny(), max_prompt_len=64, mesh=mesh
    )
    kw = dict(max_new_tokens=8, n_slots=4, prefill_chunk=16,
              speculate_k=2)
    plain = generate_batch_continuous(clf, PROMPTS, kv_quant="none", **kw)
    quant = generate_batch_continuous(clf, PROMPTS, kv_quant="int8", **kw)
    labels = [normalise_label(t) for t in quant]
    want = [normalise_label(t) for t in plain]
    agreement = np.mean([a == b for a, b in zip(labels, want)])
    assert agreement >= 0.98


def test_kv_quant_dequant_fault_degrades_byte_identical(clf):
    """Chaos drill for fault site ``kv_quant.dequant``: an int8
    scheduler degrades to the unquantized pool at construction — every
    reply byte-identical to a clean ``kv_quant="none"`` run, and the
    degrade visible in the stats block."""
    from music_analyst_tpu.resilience.faults import configure_faults

    clean = _run(_scheduler(clf, kv_quant="none"), PROMPTS)
    configure_faults("kv_quant.dequant:error@1+")
    try:
        sched = _scheduler(clf, kv_quant="int8")
    finally:
        configure_faults(None)
    assert _run(sched, PROMPTS) == clean
    kq = sched.stats()["kv_quant"]
    assert kq["degraded"] is True
    assert kq["scheme"] == "none"  # reads go through the unquantized pool
