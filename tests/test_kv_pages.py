"""Prefix-shared paged KV cache: geometry, host structures, identity.

Contract families (ISSUE 11):

* **geometry** — ``PagePlan`` validation (pow2 pages/slots, region
  alignment, pool floor) and the ``--page-size`` / ``--kv-pages``
  resolver semantics (explicit raises, malformed env falls back).
* **host structures** — ``PagePool`` refcount/free-list invariants and
  ``RadixIndex`` match/insert/evict as pure data structures, including a
  hypothesis property sweep: random arrival orders never share pages
  past the common prefix and never evict a pinned page.
* **identity** — continuous greedy text over the paged cache is
  byte-identical to static ``generate_batch`` and the monolithic
  (``page_size=0``) slot runtime, at two page sizes, under shuffled
  arrival, under eviction pressure, with copy-on-write firing, and with
  the ``kv_pages.lookup`` fault forcing full-prefill fallback.
* **zero retraces** — ``compiled_variants()`` stays at the four fixed
  programs across sharing, CoW, eviction, and slot-reuse churn.
"""

import random

import numpy as np
import pytest

from music_analyst_tpu.ops.kv_pages import (
    PagePlan,
    PagePool,
    RadixIndex,
)
from music_analyst_tpu.serving.decode_runtime import paged_runtime
from music_analyst_tpu.serving.batcher import (
    resolve_kv_pages,
    resolve_page_size,
)


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

    kwargs.setdefault("prefill_chunk", 16)
    kwargs.setdefault("prompt_region", 64)
    kwargs.setdefault("max_new_tokens", 8)
    return ContinuousScheduler(clf, **kwargs)


def _run(sched, prompts, budget=8, order=None):
    order = order if order is not None else range(len(prompts))
    reqs = {
        i: sched.submit(i, prompts[i], max_new_tokens=budget) for i in order
    }
    sched.run_until_idle()
    out = []
    for i in range(len(prompts)):
        resp = reqs[i].response or {}
        assert resp.get("ok"), resp
        out.append(resp["text"])
    return out


SHARED = "the quick brown fox jumps over the lazy dog and then "
PROMPTS = [SHARED + tail for tail in
           ("runs away", "naps", "eats a pie", "digs", "sings", "hides")]


# -------------------------------------------------------------- geometry


def test_page_plan_validation():
    plan = PagePlan(n_slots=4, prefill_chunk=16, prompt_region=64,
                    max_new=8, decode_span=4, page_size=16, n_pages=20)
    assert plan.max_total == 72
    assert plan.prompt_pages == 4 and plan.decode_pages == 1
    assert plan.pages_per_slot == 5 and plan.slot_span == 80
    assert plan.trash_page == 20  # one past the allocatable pool
    with pytest.raises(ValueError):  # non-pow2 page size
        PagePlan(n_slots=4, prefill_chunk=16, prompt_region=64,
                 max_new=8, decode_span=4, page_size=12, n_pages=20)
    with pytest.raises(ValueError):  # region not page-aligned
        PagePlan(n_slots=4, prefill_chunk=16, prompt_region=48,
                 max_new=8, decode_span=4, page_size=32, n_pages=20)
    with pytest.raises(ValueError):  # pool below one page per slot
        PagePlan(n_slots=8, prefill_chunk=16, prompt_region=64,
                 max_new=8, decode_span=4, page_size=16, n_pages=6)
    with pytest.raises(ValueError):  # pool below one resident sequence
        PagePlan(n_slots=2, prefill_chunk=16, prompt_region=64,
                 max_new=8, decode_span=4, page_size=16, n_pages=4)


def test_resolve_page_size_and_kv_pages(monkeypatch):
    assert resolve_page_size(None) == 16
    assert resolve_page_size(8) == 8
    assert resolve_page_size(0) == 0  # monolithic escape
    with pytest.raises(ValueError):
        resolve_page_size(12)  # explicit non-pow2 is a usage error
    monkeypatch.setenv("MUSICAAL_SERVE_PAGE_SIZE", "32")
    assert resolve_page_size(None) == 32
    monkeypatch.setenv("MUSICAAL_SERVE_PAGE_SIZE", "12")
    assert resolve_page_size(None) == 16  # malformed env falls back
    monkeypatch.setenv("MUSICAAL_SERVE_PAGE_SIZE", "junk")
    assert resolve_page_size(None) == 16

    assert resolve_kv_pages(None) == 0  # auto-size
    assert resolve_kv_pages(64, n_slots=8) == 64
    with pytest.raises(ValueError):
        resolve_kv_pages(4, n_slots=8)  # pool must cover the slots
    monkeypatch.setenv("MUSICAAL_SERVE_KV_PAGES", "48")
    assert resolve_kv_pages(None, n_slots=8) == 48
    monkeypatch.setenv("MUSICAAL_SERVE_KV_PAGES", "4")
    assert resolve_kv_pages(None, n_slots=8) == 0  # too-small env → auto


def test_runtime_rejects_geometry_beyond_max_seq_len(clf):
    with pytest.raises(ValueError):
        paged_runtime(clf, n_slots=2, prefill_chunk=64,
                      prompt_region=64, max_new_tokens=2048)


# ------------------------------------------------------- host structures


def test_page_pool_refcounts():
    pool = PagePool(4)
    assert pool.free_count == 4
    row = pool.alloc(3)
    assert row == [0, 1, 2]  # ascending, deterministic
    assert pool.alloc(2) is None  # insufficient — caller defers
    for p in row:
        pool.pin(p)
    pool.tree_add(row[0])
    pool.unpin(row[0])
    assert pool.free_count == 1  # held by the tree, not free
    pool.tree_drop(row[0])
    assert pool.free_count == 2  # last reference gone → free
    with pytest.raises(ValueError):
        pool.unpin(row[0])  # double release
    with pytest.raises(ValueError):
        pool.tree_drop(row[0])
    for p in row[1:]:
        pool.unpin(p)
    assert pool.free_count == 4
    pool.check()


def _slot_insert(radix, pool, ids, n_pages):
    """Insert the way the scheduler does: the slot pins its row, offers
    it to the tree at prefill-complete, and unpins at completion — pages
    the tree didn't adopt (duplicates) return to the free list."""
    row = pool.alloc(n_pages)
    assert row is not None
    for p in row:
        pool.pin(p)
    adopted = radix.insert(ids, row, pool)
    for p in row:
        pool.unpin(p)
    return row, adopted


def test_radix_match_stops_at_common_prefix():
    pool = PagePool(16)
    radix = RadixIndex(page_size=4)
    a = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]  # 2 full pages + partial [9, 10]
    pages_a, adopted = _slot_insert(radix, pool, a, 3)
    assert adopted == 3

    # Identical prompt: both full pages + the full partial run — but
    # never more tokens than the query itself holds.
    m = radix.match(a)
    assert m.pages == pages_a[:2] and m.full_tokens == 8
    assert m.partial_phys == pages_a[2] and m.partial_tokens == 2
    assert m.tokens == len(a)

    # Diverges inside page 2: only page 1 shares; the second page is
    # offered as a partial (CoW) match up to the divergence point.
    m = radix.match([1, 2, 3, 4, 5, 6, 99, 99, 9])
    assert m.pages == pages_a[:1] and m.full_tokens == 4
    assert m.partial_phys == pages_a[1] and m.partial_tokens == 2

    # Diverges in the first token: nothing shared.
    m = radix.match([99, 2, 3, 4])
    assert m.pages == [] and m.tokens == 0 and m.partial_phys is None

    # Shorter query than one page: partial match only, capped at len(q).
    m = radix.match([1, 2, 3])
    assert m.pages == [] and m.partial_phys == pages_a[0]
    assert m.partial_tokens == 3

    # Re-inserting the same prompt adopts nothing (already cached); the
    # duplicate row frees when its slot completes.
    free_before = pool.free_count
    _, adopted = _slot_insert(radix, pool, a, 3)
    assert adopted == 0
    assert pool.free_count == free_before
    pool.check()


def test_radix_evict_lru_skips_pinned():
    pool = PagePool(8)
    radix = RadixIndex(page_size=2)
    seqs = {"a": [1, 2, 3, 4], "b": [1, 2, 9, 9], "c": [5, 6]}
    pages = {}
    for name, ids in seqs.items():
        pages[name], _ = _slot_insert(radix, pool, ids, len(ids) // 2)
    # b shares a's first page, so its own contribution is pages["b"][1];
    # c's leaf is the LRU candidate once b's page is pinned by a slot.
    radix.match(seqs["c"])
    radix.match(seqs["a"])
    pool.pin(pages["b"][1])  # b's page is mapped by a live slot
    assert radix.evict(pool, 1) == 1
    assert pool.in_tree[pages["b"][1]]  # pinned page survived
    assert not pool.in_tree[pages["c"][0]]  # coldest unpinned leaf went
    # Pin a's whole chain; unpin b.  Now only b's leaf is evictable:
    # a's leaf is pinned, and the shared [1, 2] page is both pinned and
    # an interior node until its children are gone.
    pool.pin(pages["a"][0])
    pool.pin(pages["a"][1])
    pool.unpin(pages["b"][1])
    assert radix.evict(pool, 10) == 1  # only b's leaf could go
    assert pool.in_tree[pages["a"][0]] and pool.in_tree[pages["a"][1]]
    pool.unpin(pages["a"][0])
    pool.unpin(pages["a"][1])
    pool.check()


def test_radix_property_random_arrivals():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    token_seq = st.lists(st.integers(0, 3), min_size=0, max_size=12)

    @given(
        seqs=st.lists(token_seq, min_size=1, max_size=6),
        query=token_seq,
        seed=st.integers(0, 2**16),
    )
    @settings(max_examples=150, deadline=None)
    def prop(seqs, query, seed):
        P = 4
        rng = random.Random(seed)
        rng.shuffle(seqs)
        pool = PagePool(64)
        radix = RadixIndex(page_size=P)
        tokens_of = {}  # phys -> the valid tokens stored on that page
        for ids in seqs:
            n_pages = max(1, -(-len(ids) // P))
            row, _ = _slot_insert(radix, pool, ids, n_pages)
            for pi in range(n_pages):
                seg = tuple(ids[pi * P:(pi + 1) * P])
                if seg and pool.in_tree[row[pi]]:
                    tokens_of.setdefault(row[pi], seg)
        m = radix.match(query)
        # Reconstruct what the match would map and require it to be a
        # prefix of the query — sharing never goes past the common prefix.
        got = []
        for pi, phys in enumerate(m.pages):
            seg = tokens_of[phys]
            assert len(seg) == P, "full-page walk crossed a partial page"
            got.extend(seg)
        assert m.full_tokens == len(got)
        if m.partial_tokens:
            seg = tokens_of[m.partial_phys]
            assert m.partial_tokens <= len(seg)
            got.extend(seg[: m.partial_tokens])
        assert m.tokens == len(got) <= len(query)
        assert list(query[: m.tokens]) == got
        # Pinned pages survive arbitrary eviction pressure.
        pinned = [p for p in tokens_of if rng.random() < 0.5]
        for p in pinned:
            pool.pin(p)
        radix.evict(pool, pool.n_pages)
        for p in pinned:
            assert pool.in_tree[p], "evicted a pinned page"
            pool.unpin(p)
        pool.check()

    prop()


# --------------------------------------------------------------- identity


def test_monolithic_escape_matches_static(clf):
    """``page_size=0`` pins PR 10's monolithic slot runtime — the A/B
    baseline — and its text matches the static scan too, so all three
    routes produce one byte sequence."""
    static = clf.generate_batch(PROMPTS, max_new_tokens=8)
    mono = _scheduler(clf, n_slots=2, page_size=0)
    assert mono.stats()["kv_backend"] == "slots"
    assert _run(mono, PROMPTS) == static


@pytest.mark.parametrize("page_size", [8, 16])
def test_paged_matches_static(clf, page_size):
    """Byte-identical greedy text at two page sizes under shuffled
    arrival (vs the monolithic runtime too, transitively through
    test_monolithic_escape_matches_static)."""
    static = clf.generate_batch(PROMPTS, max_new_tokens=8)
    paged = _scheduler(clf, n_slots=2, page_size=page_size)
    order = list(range(len(PROMPTS)))
    random.Random(page_size).shuffle(order)
    assert _run(paged, PROMPTS, order=order) == static
    stats = paged.stats()
    assert stats["kv_backend"] == "paged"
    assert stats["page_size"] == page_size
    assert stats["prefix_cache"]["hits"] >= 1  # shared template head
    assert stats["prefix_cache"]["cow_copies"] >= 1  # unaligned boundary
    paged._pool.check()


def test_prefix_hits_skip_chunks_and_share_pages(clf):
    """Sequential arrival through few slots: later requests must hit the
    tree, skip fully-shared chunks, and still match the static scan."""
    static = clf.generate_batch(PROMPTS, max_new_tokens=8)
    sched = _scheduler(clf, n_slots=2)
    sched.warmup()
    assert _run(sched, PROMPTS) == static
    pc = sched.stats()["prefix_cache"]
    # 2 slots admit the first two cold; the remaining four arrive after
    # at least one adoption and share the common head (3 × 16-token pages).
    assert pc["lookups"] == len(PROMPTS)
    assert pc["hits"] >= len(PROMPTS) - 2
    assert pc["chunks_skipped"] >= 4
    assert pc["tokens_shared"] > 0 and pc["pages_shared"] > 0
    assert pc["bytes_saved"] > 0
    assert 0.0 < pc["hit_rate"] <= 1.0
    assert pc["fallbacks"] == 0
    sched._pool.check()


def test_identity_under_eviction_pressure(clf):
    """A pool sized for exactly two resident disjoint sequences forces
    eviction and deferred admission; text stays byte-identical."""
    prompts = [f"song number {i} is about {'x' * 40}{i}" for i in range(8)]
    static = clf.generate_batch(prompts, max_new_tokens=4)
    sched = _scheduler(clf, n_slots=2, max_new_tokens=4, kv_pages=10)
    sched.warmup()
    before = sched.runtime.compiled_variants()
    assert _run(sched, prompts, budget=4) == static
    pc = sched.stats()["prefix_cache"]
    assert pc["evictions"] > 0
    assert sched.runtime.compiled_variants() == before  # churn ≠ retrace
    sched._pool.check()


def test_zero_retraces_across_paged_workload(clf):
    """The four fixed programs never retrace as the page table churns
    through sharing, CoW, eviction, and slot reuse."""
    sched = _scheduler(clf, n_slots=4)
    record = sched.warmup()
    assert record["kv_backend"] == "paged" and record["programs"] == 4
    before = sched.runtime.compiled_variants()
    prompts = [PROMPTS[i % len(PROMPTS)] for i in range(10)]
    _run(sched, prompts, budget=6)
    assert sched.runtime.compiled_variants() == before
    assert sched.stats()["completed"] == 10
    sched._pool.check()


def test_lookup_fault_falls_back_to_full_prefill(clf):
    """A corrupted/missed radix lookup (fault site ``kv_pages.lookup``)
    degrades to zero sharing — byte-identical text, never wrong tokens."""
    from music_analyst_tpu.resilience.faults import configure_faults

    static = clf.generate_batch(PROMPTS[:4], max_new_tokens=6)
    sched = _scheduler(clf, n_slots=2)
    configure_faults("kv_pages.lookup:error@1+")
    try:
        out = _run(sched, PROMPTS[:4], budget=6)
    finally:
        configure_faults(None)
    assert out == static
    pc = sched.stats()["prefix_cache"]
    assert pc["fallbacks"] == 4 and pc["hits"] == 0
    sched._pool.check()
    # With the fault gone the same scheduler shares again.
    assert _run(sched, PROMPTS[:4], budget=6) == static
    assert sched.stats()["prefix_cache"]["hits"] >= 1


def test_scheduler_env_selects_backend(clf, monkeypatch):
    monkeypatch.setenv("MUSICAAL_SERVE_PAGE_SIZE", "0")
    mono = _scheduler(clf, n_slots=2)
    assert mono.stats()["kv_backend"] == "slots"
    assert "prefix_cache" not in mono.stats()
    monkeypatch.setenv("MUSICAAL_SERVE_PAGE_SIZE", "8")
    paged = _scheduler(clf, n_slots=2)
    st = paged.stats()
    assert st["kv_backend"] == "paged" and st["page_size"] == 8
    assert st["prefix_cache"]["enabled"]
