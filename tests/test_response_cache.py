"""Content-addressed response cache: keys, tiers, fault degradation,
and the admission-edge contract (ISSUE 20).

Contract families:

* **keys** — the cache key separates on everything that changes reply
  bytes (op, generation budget, backend fingerprint: quant schemes,
  checkpoint identity) and nothing that doesn't (whitespace variants
  fold through the shared ``normalize_text`` identity contract).
* **tiers** — cold → warm → cross-restart round trip through the
  memory LRU and the on-disk tier; cached replies are byte-identical
  to computed ones (the ``cached`` stamp lives in stats/trace, never
  the payload).
* **never wrong** — truncated or CRC-flipped entries are detected,
  evicted, and recomputed; injected read faults degrade to recompute
  WITHOUT evicting (transient ≠ corrupt); injected write faults leave
  the settle uncached.  Sites ``response_cache.read`` and
  ``response_cache.write`` (resilience/faults.py roster).
* **admission edge** — hits run before the shed ladder (a would-shed
  repeat is answered, not rejected), charge zero tenant tokens and
  zero engine-ledger chip-seconds, and trigger zero retraces of the
  compiled decode programs; journal dedup (re-sent id) and response
  cache (same text, NEW id) compose without double answers.
"""

import io
import json
import os

import pytest

from music_analyst_tpu.serving.response_cache import (
    CACHEABLE_OPS,
    ResponseCache,
    backend_fingerprint,
    checkpoint_stamp,
    normalize_text,
    resolve_response_cache_dir,
    response_key,
    try_answer,
)


@pytest.fixture(scope="module")
def mock_backend():
    from music_analyst_tpu.models.backend import ModelResidency

    return ModelResidency(model="mock", mock=True).acquire()


@pytest.fixture(scope="module")
def ops(mock_backend):
    from music_analyst_tpu.serving.server import build_ops

    return build_ops(mock_backend)


@pytest.fixture(scope="module")
def clf():
    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )

    return LlamaZeroShotClassifier(
        config=LlamaConfig.tiny(), max_prompt_len=64
    )


def _batcher(ops, cache=None, **kwargs):
    from music_analyst_tpu.serving.batcher import DynamicBatcher

    kwargs.setdefault("max_batch", 4)
    kwargs.setdefault("max_wait_ms", 2.0)
    kwargs.setdefault("max_queue", 64)
    return DynamicBatcher(ops, response_cache=cache, **kwargs)


def _settled(reqs, timeout=60.0):
    out = []
    for req in reqs:
        assert req.wait(timeout=timeout), f"request {req.id} never settled"
        out.append(dict(req.response))
    return out


def _sans_id(payload):
    return {k: v for k, v in payload.items() if k != "id"}


TEXTS = [
    "sunshine and happy days by the golden river",
    "tears and sorrow in the lonely broken night",
    "la la la the radio plays our song again",
]


# ------------------------------------------------------------------- keys


def test_normalize_text_is_the_shared_identity_contract():
    assert normalize_text("  I  love\tthis \n song ") == "I love this song"
    assert normalize_text("I love this song") == "I love this song"
    assert normalize_text("") == ""


def test_key_separates_on_everything_that_changes_bytes():
    fp = backend_fingerprint(model="llama", weight_quant="int8")
    base = response_key("hello world", "generate", 16, fp)
    # Whitespace variants fold; anything output-relevant separates.
    assert response_key(" hello \t world ", "generate", 16, fp) == base
    assert response_key("hello worlds", "generate", 16, fp) != base
    assert response_key("hello world", "sentiment", 16, fp) != base
    assert response_key("hello world", "generate", 8, fp) != base
    assert response_key("hello world", "generate", None, fp) != base
    for other in (
        backend_fingerprint(model="llama", weight_quant="int4"),
        backend_fingerprint(model="llama", weight_quant="int8",
                            kv_quant="int8"),
        backend_fingerprint(model="llama", weight_quant="int8",
                            checkpoint="ckpt:1:2"),
        backend_fingerprint(model="distilbert", weight_quant="int8"),
    ):
        assert response_key("hello world", "generate", 16, other) != base


def test_backend_fingerprint_drops_none_and_sorts():
    assert backend_fingerprint(b="2", a="1") == "a=1;b=2"
    assert backend_fingerprint(a="1", gone=None) == "a=1"
    # absent ≠ empty: an unset knob and an empty one are different backends
    assert backend_fingerprint(a="") != backend_fingerprint()


def test_checkpoint_stamp_rekeys_on_swapped_weights(tmp_path, monkeypatch):
    monkeypatch.delenv("MUSICAAL_LLAMA_CKPT", raising=False)
    monkeypatch.delenv("MUSICAAL_LLAMA_TOKENIZER", raising=False)
    monkeypatch.delenv("MUSICAAL_DISTILBERT_CKPT", raising=False)
    monkeypatch.delenv("MUSICAAL_BERT_VOCAB", raising=False)
    assert checkpoint_stamp() is None  # mock/synthetic: no real weights
    ckpt = tmp_path / "model.ckpt"
    ckpt.write_bytes(b"v1")
    monkeypatch.setenv("MUSICAAL_LLAMA_CKPT", str(ckpt))
    first = checkpoint_stamp()
    assert first and str(ckpt) in first
    ckpt.write_bytes(b"version two")  # swapped in place: size changes
    assert checkpoint_stamp() != first


def test_resolve_dir_precedence(tmp_path, monkeypatch):
    monkeypatch.delenv("MUSICAAL_RESPONSE_CACHE", raising=False)
    default = resolve_response_cache_dir()
    assert default and default.endswith("musicaal_responses")
    monkeypatch.setenv("MUSICAAL_RESPONSE_CACHE", str(tmp_path))
    assert resolve_response_cache_dir() == str(tmp_path)
    assert resolve_response_cache_dir("/explicit") == "/explicit"
    monkeypatch.setenv("MUSICAAL_RESPONSE_CACHE", "off")
    assert resolve_response_cache_dir() is None
    monkeypatch.setenv("MUSICAAL_RESPONSE_CACHE", str(tmp_path))
    assert resolve_response_cache_dir(use_cache=False) is None


# ------------------------------------------------------------------ tiers


def test_cold_warm_cross_restart_roundtrip(tmp_path):
    d = str(tmp_path / "rc")
    cache = ResponseCache(d, fingerprint="fp")
    key = cache.key_for("sentiment", "sunny song")
    assert cache.lookup(key) is None  # cold
    payload = {"id": "r1", "ok": True, "op": "sentiment",
               "label": "Positive"}
    assert cache.put(key, payload)
    got = cache.lookup(key)  # warm: memory tier
    assert got == {"ok": True, "op": "sentiment", "label": "Positive"}
    assert "id" not in got  # identity belongs to the request
    stats = cache.stats()
    assert stats["mem_hits"] == 1 and stats["stores"] == 1

    restarted = ResponseCache(d, fingerprint="fp")  # cross-restart
    got2 = restarted.lookup(key)
    assert got2 == got
    assert restarted.stats()["disk_hits"] == 1
    assert restarted.lookup(key) is not got2  # copies, not aliases
    got2["label"] = "poisoned"
    assert restarted.lookup(key)["label"] == "Positive"


def test_put_rejects_errors_and_never_raises(tmp_path):
    cache = ResponseCache(str(tmp_path), fingerprint="fp")
    key = cache.key_for("sentiment", "x")
    assert not cache.put(key, {"id": "a", "ok": False,
                               "error": {"kind": "queue_full"}})
    assert not cache.put(key, "not a dict")
    assert cache.lookup(key) is None


def test_mem_lru_bound_and_disk_byte_budget_eviction(tmp_path):
    cache = ResponseCache(str(tmp_path), fingerprint="fp",
                          mem_entries=2, max_bytes=300)
    keys = []
    for i in range(6):
        key = cache.key_for("sentiment", f"song number {i}")
        cache.put(key, {"ok": True, "label": f"L{i}"})
        keys.append(key)
    assert cache.stats()["mem_entries"] == 2  # LRU front tier bounded
    assert cache.stats()["evictions"] > 0  # disk tier held to max_bytes
    on_disk = [n for n in os.listdir(tmp_path) if n.endswith(".json")]
    total = sum(
        os.path.getsize(os.path.join(tmp_path, n)) for n in on_disk
    )
    assert total <= 300


def test_uncacheable_ops_pass_through(ops):
    cache = ResponseCache(None, fingerprint="fp")
    assert "stats" not in CACHEABLE_OPS

    class _Req:
        op = "stats"
        text = ""
        id = "s"
        meta = {}

    assert try_answer(cache, _Req()) is False
    assert cache.stats()["lookups"] == 0


# ----------------------------------------------- byte identity (sentiment)


def test_sentiment_cached_replies_byte_identical_no_dispatch(
    ops, tmp_path
):
    d = str(tmp_path / "rc")
    control = _batcher(ops).start()
    want = _settled(
        [control.submit(f"r{i}", "sentiment", t)
         for i, t in enumerate(TEXTS)]
    )
    control.drain()

    cache = ResponseCache(d, fingerprint=backend_fingerprint(model="mock"))
    cold = _batcher(ops, cache).start()
    got_cold = _settled(
        [cold.submit(f"r{i}", "sentiment", t)
         for i, t in enumerate(TEXTS)]
    )
    cold.drain()
    assert got_cold == want  # same serialized fields, same order

    # Fresh batcher + restarted cache: every reply comes from disk, the
    # wire payload is byte-for-byte the computed one, and the device is
    # never dispatched (zero batches, zero rows).
    warm_cache = ResponseCache(
        d, fingerprint=backend_fingerprint(model="mock")
    )
    warm = _batcher(ops, warm_cache).start()
    got_warm = _settled(
        [warm.submit(f"r{i}", "sentiment", t)
         for i, t in enumerate(TEXTS)]
    )
    stats = warm.stats()
    warm.drain()
    assert [json.dumps(r, sort_keys=False) for r in got_warm] == [
        json.dumps(r, sort_keys=False) for r in want
    ]
    assert stats["cache_hits"] == len(TEXTS)
    assert stats["batches"] == 0 and stats["rows"] == 0
    assert stats["admitted"] == 0  # hits never enter the queue
    assert stats["response_cache"]["hit_rate"] == 1.0
    # the ``cached`` stamp is metadata, never payload
    assert all("cached" not in r for r in got_warm)


def test_whitespace_variant_hits_same_entry(ops, tmp_path):
    cache = ResponseCache(str(tmp_path), fingerprint="fp")
    b = _batcher(ops, cache).start()
    first = _settled([b.submit("a", "sentiment", "happy  song")])[0]
    second = _settled([b.submit("b", "sentiment", " happy\tsong ")])[0]
    stats = b.stats()
    b.drain()
    assert stats["cache_hits"] == 1
    assert _sans_id(second) == _sans_id(first)


# ----------------------------------- byte identity + zero cost (generate)


def test_generate_cached_replies_byte_identical_zero_chip_seconds(
    clf, tmp_path
):
    from music_analyst_tpu.serving.decode_loop import ContinuousScheduler

    kw = dict(n_slots=2, prefill_chunk=16, prompt_region=64,
              max_new_tokens=8, max_queue=32)
    prompts = ["golden sunshine on the river", "rain falls tonight"]

    control = ContinuousScheduler(clf, **kw)
    control.warmup()
    creqs = [
        control.submit(f"c{i}", p, max_new_tokens=8, tenant="gold")
        for i, p in enumerate(prompts)
    ]
    control.run_until_idle()
    want = [_sans_id(r) for r in _settled(creqs)]

    cache = ResponseCache(str(tmp_path / "rc"), fingerprint="llama-tiny")
    sched = ContinuousScheduler(clf, response_cache=cache, **kw)
    sched.warmup()
    variants0 = sched.runtime.compiled_variants()
    reqs = [
        sched.submit(f"a{i}", p, max_new_tokens=8, tenant="gold")
        for i, p in enumerate(prompts)
    ]
    sched.run_until_idle()
    assert [_sans_id(r) for r in _settled(reqs)] == want
    chip0 = sched.slo_snapshot()["tenants"]["gold"]["chip_seconds"]
    assert chip0 > 0.0

    # Warm repeats: answered in submit — byte-identical, zero new
    # chip-seconds billed, zero retraces, decode loop never ticks.
    repeats = [
        sched.submit(f"b{i}", p, max_new_tokens=8, tenant="gold")
        for i, p in enumerate(prompts)
    ]
    assert all(r.done for r in repeats)  # settled without run_until_idle
    assert [_sans_id(r) for r in _settled(repeats)] == want
    stats = sched.stats()
    assert stats["cache_hits"] == len(prompts)
    assert sched.slo_snapshot()["tenants"]["gold"]["chip_seconds"] == chip0
    assert sched.runtime.compiled_variants() == variants0

    # A different budget is a different answer: must miss, not hit.
    other = sched.submit("d0", prompts[0], max_new_tokens=4, tenant="gold")
    assert not other.done
    sched.run_until_idle()
    assert _settled([other])[0]["ok"]
    assert sched.stats()["cache_hits"] == len(prompts)  # unchanged


# ------------------------------------------------------------- never wrong


def test_truncated_entry_detected_evicted_recomputed(ops, tmp_path):
    d = str(tmp_path)
    cache = ResponseCache(d, fingerprint="fp")
    key = cache.key_for("sentiment", TEXTS[0])
    cache.put(key, {"ok": True, "op": "sentiment", "label": "Positive"})
    path = os.path.join(d, f"{key}.json")
    blob = open(path, "r", encoding="utf-8").read()
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(blob[: len(blob) // 2])  # torn write

    fresh = ResponseCache(d, fingerprint="fp")
    assert fresh.lookup(key) is None  # degraded to miss, never wrong
    assert fresh.stats()["corrupt"] == 1
    assert not os.path.exists(path)  # corrupt entries are evicted

    # The miss path recomputes and republishes.
    b = _batcher(ops, fresh).start()
    reply = _settled([b.submit("r", "sentiment", TEXTS[0])])[0]
    b.drain()
    assert reply["ok"] and os.path.exists(path)


def test_crc_flip_detected_evicted_never_served(tmp_path):
    d = str(tmp_path)
    cache = ResponseCache(d, fingerprint="fp")
    key = cache.key_for("sentiment", "tampered song")
    cache.put(key, {"ok": True, "label": "Positive"})
    path = os.path.join(d, f"{key}.json")
    record = json.load(open(path, "r", encoding="utf-8"))
    record["payload"]["label"] = "Negative"  # flipped bytes, stale CRC
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(record, fh)

    fresh = ResponseCache(d, fingerprint="fp")
    assert fresh.lookup(key) is None
    assert fresh.stats()["corrupt"] == 1
    assert not os.path.exists(path)


def test_read_fault_falls_back_without_evicting(tmp_path):
    from music_analyst_tpu.resilience import configure_faults, fault_stats

    d = str(tmp_path)
    cache = ResponseCache(d, fingerprint="fp")
    key = cache.key_for("sentiment", "faulted read song")
    cache.put(key, {"ok": True, "label": "Positive"})
    path = os.path.join(d, f"{key}.json")

    fresh = ResponseCache(d, fingerprint="fp")
    configure_faults("response_cache.read:error@1")
    try:
        assert fresh.lookup(key) is None  # transient: degrade to compute
        trips = fault_stats()["response_cache.read"]["trips"]
    finally:
        configure_faults(None)
    assert trips == 1
    assert fresh.stats()["read_fallbacks"] == 1
    assert fresh.stats()["corrupt"] == 0
    assert os.path.exists(path)  # transient ≠ corrupt: NOT evicted
    assert fresh.lookup(key) == {"ok": True, "label": "Positive"}


def test_write_fault_leaves_settle_uncached(tmp_path):
    from music_analyst_tpu.resilience import configure_faults, fault_stats

    d = str(tmp_path)
    cache = ResponseCache(d, fingerprint="fp")
    key = cache.key_for("sentiment", "faulted write song")
    configure_faults("response_cache.write:error@1")
    try:
        cache.put(key, {"ok": True, "label": "Positive"})
        trips = fault_stats()["response_cache.write"]["trips"]
    finally:
        configure_faults(None)
    assert trips == 1
    assert cache.stats()["write_errors"] == 1
    assert not os.path.exists(os.path.join(d, f"{key}.json"))
    # The memory tier still answered this process; a restart recomputes.
    assert cache.lookup(key) is not None
    assert ResponseCache(d, fingerprint="fp").lookup(key) is None


# --------------------------------------------------------- admission edge


def test_hits_never_charged_to_tenant_bucket(ops, tmp_path):
    cache = ResponseCache(str(tmp_path), fingerprint="fp")
    b = _batcher(ops, cache, tenant_budget=1.0).start()
    prime = _settled([b.submit("p", "sentiment", TEXTS[0],
                               tenant="miser")])[0]
    assert prime["ok"]
    # Burst far past the 1 req/s bucket (burst 2): every repeat hits and
    # none touches the bucket, so nothing sheds.
    reqs = [
        b.submit(f"h{i}", "sentiment", TEXTS[0], tenant="miser")
        for i in range(10)
    ]
    replies = _settled(reqs)
    stats = b.stats()
    b.drain()
    assert all(r["ok"] for r in replies)
    assert stats["cache_hits"] == 10
    assert stats["shed_tenant_budget"] == 0
    # An uncached text from the same tenant still meters normally.
    b2 = _batcher(ops, cache, tenant_budget=1.0)
    for i in range(3):
        b2.submit(f"u{i}", "sentiment", f"fresh uncached text {i}",
                  tenant="miser")
    assert b2.stats()["shed_tenant_budget"] > 0


def test_would_shed_request_is_answered_from_cache(ops, tmp_path):
    cache = ResponseCache(str(tmp_path), fingerprint="fp")
    primer = _batcher(ops, cache).start()
    _settled([primer.submit("p", "sentiment", TEXTS[0])])
    primer.drain()

    # Unstarted batcher with a one-deep queue: the first uncached submit
    # fills it, the second sheds queue_full — but the cached repeat is
    # answered BEFORE the shed ladder ever runs.
    b = _batcher(ops, cache, max_queue=1)
    queued = b.submit("q", "sentiment", "uncached filler text")
    assert not queued.done
    shed = b.submit("s", "sentiment", "another uncached text")
    assert shed.response["error"]["kind"] == "queue_full"
    hit = b.submit("h", "sentiment", TEXTS[0])
    assert hit.done and hit.response["ok"]
    stats = b.stats()
    assert stats["cache_hits"] == 1
    assert stats["shed_queue_full"] == 1  # only the uncached one


def test_journal_dedup_and_response_cache_compose(ops, tmp_path):
    """Re-sent id → journal dedup (never reaches the cache); same text
    under a NEW id → response-cache hit.  Exactly-once is unchanged and
    every cached reply is journaled like a computed one."""
    from music_analyst_tpu.serving.journal import RequestJournal
    from music_analyst_tpu.serving.server import SentimentServer

    journal = RequestJournal(str(tmp_path / "wal"))
    journal.recover()
    cache = ResponseCache(str(tmp_path / "rc"), fingerprint="fp")
    # Stream 1 computes and journals id "a"; stream 2 (a re-dispatching
    # client against a restarted server — the journal's wire contract)
    # re-sends "a" and sends the same text under the NEW id "b".
    first = [json.dumps({"id": "a", "op": "sentiment", "text": TEXTS[0]})]
    second = [
        json.dumps({"id": "a", "op": "sentiment", "text": TEXTS[0]}),
        json.dumps({"id": "b", "op": "sentiment", "text": TEXTS[0]}),
    ]
    out = io.StringIO()
    batcher2 = None
    for lines in (first, second):
        batcher2 = _batcher(ops, cache).start()
        server = SentimentServer(batcher2, mode="stdio", journal=journal)
        server.handle_stream(
            io.StringIO("".join(line + "\n" for line in lines)),
            out, drain_on_eof=True,
        )
    replies = [json.loads(line) for line in out.getvalue().splitlines()]
    assert [r["id"] for r in replies] == ["a", "a", "b"]
    assert _sans_id(replies[1]) == _sans_id(replies[0])
    assert _sans_id(replies[2]) == _sans_id(replies[0])
    assert journal.stats()["deduped"] == 1  # the re-sent id
    assert batcher2.stats()["cache_hits"] == 1  # only the new-id repeat
    # The cached reply was journaled: a restart dedups id "b" too.
    journal.close()
    j2 = RequestJournal(str(tmp_path / "wal"))
    j2.recover()
    assert _sans_id(j2.lookup_reply("b")) == _sans_id(replies[2])
    j2.close()


def test_stats_snapshot_carries_response_cache_section(ops, tmp_path):
    from music_analyst_tpu.serving.server import SentimentServer

    cache = ResponseCache(str(tmp_path), fingerprint="fp")
    batcher = _batcher(ops, cache).start()
    server = SentimentServer(batcher, mode="stdio")
    _settled([batcher.submit("x", "sentiment", TEXTS[0])])
    _settled([batcher.submit("y", "sentiment", TEXTS[0])])
    snap = server.stats_snapshot()
    batcher.drain()
    rc = snap["response_cache"]
    assert rc["lookups"] == 2 and rc["hits"] == 1
    assert rc["hit_rate"] == 0.5
    assert rc["dedup_factor"] > 1.0
    assert "bytes" in rc and "evictions" in rc
