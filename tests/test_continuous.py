"""Continuous-batching decode runtime: equivalence, isolation, resilience.

Contract families (ISSUE 10):

* **equivalence** — the slot runtime's greedy tokens are byte-identical
  to the static ``generate_batch`` scan for the same prompts, at
  ``n_slots`` ∈ {2, 8}, under randomized arrival order, and with the
  early-exit static scan on or off; zero-shot labels agree between the
  static and continuous classify paths.
* **slots** — reuse across more requests than slots never leaks one
  sequence's KV into another; per-request budgets truncate exactly.
* **resilience** — a poison prompt fails alone while co-resident slots
  finish; a persistent decode fault fails the in-flight requests with
  structured errors and the scheduler keeps serving; a stalled decode
  dispatch trips the watchdog with taxonomy ``decode_stall``; zero
  retraces of the fixed compiled programs across a whole workload.

Paged-cache-specific contracts (page pool, radix tree, prefix sharing)
live in tests/test_kv_pages.py; this file exercises the default (paged)
backend through the same scheduler API it always had.
"""

import json
import random
import time

import pytest

from music_analyst_tpu.serving.batcher import (
    resolve_prefill_chunk,
    resolve_slots,
)
from music_analyst_tpu.serving.decode_loop import generate_batch_continuous
from music_analyst_tpu.serving.decode_runtime import slot_runtime
from music_analyst_tpu.utils.labels import normalise_label


@pytest.fixture(scope="module")
def clf():
    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )

    return LlamaZeroShotClassifier(
        config=LlamaConfig.tiny(), max_prompt_len=64
    )


PROMPTS = [
    "golden sunshine on the river",
    "rain",
    "shadows fall across the empty street tonight",
    "my heart beats a broken drum",
    "la la la la",
    "winter wind and summer fire",
    "ok",
    "the long road home winds past the silver lake and over the hills",
]


def _scheduler(clf, **kwargs):
    from music_analyst_tpu.serving.decode_loop import ContinuousScheduler

    kwargs.setdefault("prefill_chunk", 16)
    kwargs.setdefault("prompt_region", 64)
    kwargs.setdefault("max_new_tokens", 8)
    kwargs.setdefault("max_queue", 64)
    return ContinuousScheduler(clf, **kwargs)


def _run(sched, prompts, budgets=None):
    budgets = budgets or [sched.plan.max_new] * len(prompts)
    reqs = [
        sched.submit(i, prompt, max_new_tokens=budget)
        for i, (prompt, budget) in enumerate(zip(prompts, budgets))
    ]
    sched.run_until_idle()
    out = []
    for req in reqs:
        resp = req.response or {}
        assert resp.get("ok"), resp
        out.append(resp)
    return out


# -------------------------------------------------------------- geometry


def test_resolve_slots_and_prefill_chunk(monkeypatch):
    assert resolve_slots(None) == 8
    assert resolve_slots(5) == 8  # rounded up to a power of two
    monkeypatch.setenv("MUSICAAL_SERVE_SLOTS", "4")
    assert resolve_slots(None) == 4
    monkeypatch.setenv("MUSICAAL_SERVE_SLOTS", "junk")
    assert resolve_slots(None) == 8  # malformed env falls back
    assert resolve_prefill_chunk(None) == 64
    monkeypatch.setenv("MUSICAAL_SERVE_PREFILL_CHUNK", "32")
    assert resolve_prefill_chunk(None) == 32
    with pytest.raises(ValueError):
        resolve_slots("junk")  # explicit value is a usage error


def test_slot_plan_validation():
    from music_analyst_tpu.ops.kv_slots import SlotPlan

    plan = SlotPlan(n_slots=4, prefill_chunk=16, prompt_region=64,
                    max_new=8, decode_span=4)
    assert plan.max_total == 72
    with pytest.raises(ValueError):
        SlotPlan(n_slots=3, prefill_chunk=16, prompt_region=64,
                 max_new=8, decode_span=4)
    with pytest.raises(ValueError):
        SlotPlan(n_slots=4, prefill_chunk=24, prompt_region=64,
                 max_new=8, decode_span=4)
    with pytest.raises(ValueError):
        SlotPlan(n_slots=4, prefill_chunk=16, prompt_region=64,
                 max_new=0, decode_span=4)


def test_runtime_rejects_geometry_beyond_max_seq_len(clf):
    # prompt_region clamps to max_prompt_len, so the overflow has to come
    # from the decode budget: 64 + 2048 > tiny's max_seq_len of 2048.
    with pytest.raises(ValueError):
        slot_runtime(clf, n_slots=2, prefill_chunk=64,
                     prompt_region=64, max_new_tokens=2048)


# ----------------------------------------------------------- equivalence


@pytest.mark.parametrize("n_slots", [2, 8])
def test_continuous_matches_static_greedy(clf, n_slots):
    """Byte-identical greedy text per prompt, randomized arrival order."""
    static = clf.generate_batch(PROMPTS, max_new_tokens=8)
    sched = _scheduler(clf, n_slots=n_slots)
    order = list(range(len(PROMPTS)))
    random.Random(n_slots).shuffle(order)
    reqs = {
        i: sched.submit(i, PROMPTS[i], max_new_tokens=8) for i in order
    }
    sched.run_until_idle()
    for i, want in enumerate(static):
        resp = reqs[i].response
        assert resp["ok"], resp
        assert resp["text"] == want, f"prompt {i} diverged"
    assert sched.stats()["completed"] == len(PROMPTS)


def test_generate_batch_continuous_wrapper_matches_static(clf):
    static = clf.generate_batch(PROMPTS, max_new_tokens=6)
    cont = generate_batch_continuous(clf, PROMPTS, max_new_tokens=6, n_slots=2, prefill_chunk=16
    )
    assert cont == static


def test_early_exit_scan_matches_full_scan(clf):
    full = clf.generate_batch(PROMPTS, max_new_tokens=8, early_exit=False)
    early = clf.generate_batch(PROMPTS, max_new_tokens=8, early_exit=True)
    assert early == full


def test_zero_shot_labels_agree_static_vs_continuous(clf):
    texts = ["I love this sunny day", "so sad and lonely", "whatever"]
    static = clf.classify_batch_by_generation(texts)
    from music_analyst_tpu.models.llama import zero_shot_prompt

    # the same prompts, budget and normaliser, decoded by the continuous
    # runtime
    generations = generate_batch_continuous(
        clf, [zero_shot_prompt(t) for t in texts], max_new_tokens=16,
        n_slots=2)
    continuous = [
        "Neutral" if not text.strip() else normalise_label(gen)
        for text, gen in zip(texts, generations)
    ]
    assert continuous == static


# ----------------------------------------------------------------- slots


def test_slot_reuse_is_isolated(clf):
    """3× more requests than slots, twice in different interleavings:
    outputs depend only on the prompt, never on which slot served it or
    what lived there before."""
    prompts = [PROMPTS[i % len(PROMPTS)] for i in range(12)]
    sched = _scheduler(clf, n_slots=4)
    first = [r["text"] for r in _run(sched, prompts)]
    second = [r["text"] for r in _run(sched, list(reversed(prompts)))]
    assert first == list(reversed(second))
    # Identical prompts through different slots give identical text.
    assert first[0] == first[8] and first[3] == first[11]


def test_budgets_truncate_per_request(clf):
    sched = _scheduler(clf, n_slots=2)
    full = _run(sched, PROMPTS[:4])
    short = _run(sched, PROMPTS[:4], budgets=[2, 8, 1, 3])
    for resp, budget in zip(short, [2, 8, 1, 3]):
        assert resp["tokens"] <= budget
    # The row whose budget equals the full budget is byte-identical.
    assert short[1]["text"] == full[1]["text"]


def test_zero_retraces_across_workload(clf):
    sched = _scheduler(clf, n_slots=4)
    sched.warmup()
    before = sched.runtime.compiled_variants()
    _run(sched, [PROMPTS[i % len(PROMPTS)] for i in range(10)],
         budgets=[1 + i % 7 for i in range(10)])
    assert sched.runtime.compiled_variants() == before
    assert sched.stats()["completed"] == 10


# ------------------------------------------------------------ resilience


def test_poison_prompt_fails_alone(clf, monkeypatch):
    from music_analyst_tpu.resilience.faults import InjectedFatal
    from music_analyst_tpu.serving import decode_loop

    sched = _scheduler(clf, n_slots=2)
    clean = [r["text"] for r in _run(sched, PROMPTS[:4])]

    real = decode_loop.ContinuousScheduler._device_prefill

    def poisoned(self, idx, slot):
        if "POISON" in slot.req.text:
            raise InjectedFatal("decode.step", 0)
        return real(self, idx, slot)

    monkeypatch.setattr(
        decode_loop.ContinuousScheduler, "_device_prefill", poisoned
    )
    prompts = PROMPTS[:2] + ["POISON pill"] + PROMPTS[2:4]
    reqs = [sched.submit(i, p) for i, p in enumerate(prompts)]
    sched.run_until_idle()
    responses = [r.response for r in reqs]
    assert not responses[2]["ok"]
    assert responses[2]["error"]["kind"] == "request_failed"
    survivors = [responses[i]["text"] for i in (0, 1, 3, 4)]
    assert survivors == clean  # co-resident slots finished, byte-equal


def test_persistent_decode_failure_is_structured_and_survivable(clf):
    from music_analyst_tpu.resilience import configure_faults

    sched = _scheduler(clf, n_slots=2)
    configure_faults("decode.step:fatal")
    try:
        reqs = [sched.submit(i, p) for i, p in enumerate(PROMPTS[:2])]
        sched.run_until_idle()
        for req in reqs:
            assert not req.response["ok"]
            assert req.response["error"]["kind"] == "request_failed"
    finally:
        configure_faults(None)
    # The scheduler survives: the very next workload succeeds.
    texts = [r["text"] for r in _run(sched, PROMPTS[:2])]
    assert texts == clf.generate_batch(PROMPTS[:2], max_new_tokens=8)
    assert sched.stats()["failed"] == 2


def test_transient_decode_fault_is_retried(clf):
    from music_analyst_tpu.resilience import (
        configure_faults,
        reset_retry_stats,
        retry_stats,
    )

    sched = _scheduler(clf, n_slots=2)
    reset_retry_stats()
    configure_faults("decode.step:error@1")
    try:
        out = _run(sched, PROMPTS[:2])
    finally:
        configure_faults(None)
    assert all(r["ok"] for r in out)
    assert retry_stats()["decode.step"]["retries"] >= 1


def test_decode_stall_trips_watchdog(clf):
    from music_analyst_tpu.observability.watchdog import (
        start_watchdog,
        stop_watchdog,
    )
    from music_analyst_tpu.resilience import configure_faults

    wd = start_watchdog(0.3)
    configure_faults("decode.step:delay=1s@1")
    try:
        out = _run(sched := _scheduler(clf, n_slots=2), PROMPTS[:1])
    finally:
        configure_faults(None)
        stop_watchdog()
    assert out[0]["ok"]
    assert any(t["taxonomy"] == "decode_stall" for t in wd.trips), wd.trips
    assert sched.stats()["completed"] == 1


def test_decode_stall_classifies_in_report():
    from music_analyst_tpu.observability.report import classify_error

    assert classify_error("watchdog: decode_stall in decode.dispatch") == \
        "decode_stall"


# ------------------------------------------------- admission + protocol


def test_admission_sheds_queue_full_and_draining(clf):
    # Distinct texts: identical in-flight generates would fold at the
    # dedup edge (tests/test_speculative.py) instead of ever queueing.
    sched = _scheduler(clf, n_slots=2, max_queue=2)
    blocked = [
        sched.submit(i, f"text {i}", max_new_tokens=1) for i in range(3)
    ]
    shed = blocked[2]
    assert shed.done and shed.response["error"]["kind"] == "queue_full"
    sched.run_until_idle()
    assert all(b.response["ok"] for b in blocked[:2])
    sched.drain()
    late = sched.submit("late", "text")
    assert late.response["error"]["kind"] == "draining"
    assert sched.stats()["shed"] == 2


def test_server_stats_and_generate_op(clf):
    """In-process stdio server: a generate request between two sentiment
    requests answers in order, and `stats` exposes the decode gauges."""
    import io

    from music_analyst_tpu.serving.batcher import DynamicBatcher
    from music_analyst_tpu.serving.server import SentimentServer, build_ops

    sched = _scheduler(clf, n_slots=2).start()
    batcher = DynamicBatcher(
        build_ops(clf), max_batch=2, max_wait_ms=2.0, max_queue=16
    ).start()
    server = SentimentServer(batcher, None, mode="stdio", decode=sched)
    lines = [
        {"id": "a", "op": "sentiment", "text": "happy joy"},
        {"id": "b", "op": "generate", "text": "sunny", "max_new_tokens": 3},
        {"id": "c", "op": "sentiment", "text": "sad rain"},
        {"id": "d", "op": "stats"},
        {"id": "e", "op": "generate", "text": "x", "max_new_tokens": "no"},
    ]
    wfile = io.StringIO()
    rfile = io.StringIO("".join(json.dumps(l) + "\n" for l in lines))
    server.handle_stream(rfile, wfile, drain_on_eof=True)
    replies = [json.loads(l) for l in wfile.getvalue().splitlines()]
    assert [r["id"] for r in replies] == ["a", "b", "c", "d", "e"]
    gen = replies[1]
    assert gen["ok"] and gen["op"] == "generate"
    assert "text" in gen and "label" in gen and gen["tokens"] <= 3
    stats = replies[3]["stats"]["decode"]
    for key in ("active_slots", "free_slots", "prefill_backlog",
                "tokens_generated", "ttft", "tpot", "slot_occupancy_hist"):
        assert key in stats, key
    assert replies[4]["error"]["kind"] == "bad_request"


def test_generate_without_slot_runtime_is_bad_request():
    import io

    from music_analyst_tpu.serving.batcher import DynamicBatcher
    from music_analyst_tpu.serving.server import SentimentServer

    batcher = DynamicBatcher(
        {"echo": lambda texts: [{"text": t} for t in texts]},
        max_batch=2, max_wait_ms=2.0, max_queue=4,
    ).start()
    server = SentimentServer(batcher, None, mode="stdio", decode=None)
    wfile = io.StringIO()
    rfile = io.StringIO(
        json.dumps({"id": 1, "op": "generate", "text": "hi"}) + "\n"
    )
    server.handle_stream(rfile, wfile, drain_on_eof=True)
    reply = json.loads(wfile.getvalue())
    assert not reply["ok"]
    assert reply["error"]["kind"] == "bad_request"


def test_threaded_scheduler_settles_and_drains(clf):
    sched = _scheduler(clf, n_slots=2).start()
    reqs = [sched.submit(i, p, max_new_tokens=4)
            for i, p in enumerate(PROMPTS[:4])]
    for req in reqs:
        assert req.wait(timeout=60.0), "request never settled"
        assert req.response["ok"]
    sched.drain()
    assert sched.stats()["completed"] == 4


def test_ttft_tpot_quantiles_populated(clf):
    sched = _scheduler(clf, n_slots=2)
    _run(sched, PROMPTS[:4])
    stats = sched.stats()
    assert stats["ttft"]["count"] == 4
    assert stats["ttft"]["p50_s"] > 0
    assert stats["tpot"]["count"] >= 1
    assert stats["tokens_per_s"] > 0


def test_decode_warmup_compiles_before_first_request(clf):
    # Default backend is the paged cache: four fixed programs (prefill,
    # decode, free, copy-on-write).  page_size=0 pins PR 10's monolithic
    # slot cache and its five (prefill, decode, free, plus the
    # checkpoint snapshot/restore pair).
    sched = _scheduler(clf, n_slots=2)
    record = sched.warmup()
    assert record["kv_backend"] == "paged"
    assert record["programs"] == 4 and record["seconds"] > 0
    variants = sched.runtime.compiled_variants()
    _run(sched, PROMPTS[:2])
    assert sched.runtime.compiled_variants() == variants

    mono = _scheduler(clf, n_slots=2, page_size=0)
    record = mono.warmup()
    assert record["kv_backend"] == "slots"
    assert record["programs"] == 5
