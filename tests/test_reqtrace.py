"""Per-request distributed tracing (ISSUE 16).

Contract families:

* **resolve/sampling** — flag > env > default; malformed flag is a
  usage error, malformed env falls back; head sampling is a
  deterministic function of the trace id.
* **zero effect when disabled** — no ``trace_id`` on any reply, no
  trace file, no extra meta, byte-for-byte the untraced wire.
* **waterfall** — a traced stdio generate request yields >=6 phases
  whose span sum covers >=95% of its measured wire latency;
  ``trace-report`` reconstructs it (exit 0) and the manifest's
  ``trace_exemplars`` ids resolve to complete waterfalls.
* **tail sampling** — sheds, preemptions and failures always flush,
  with the keep reason on the record; a preempted+resumed request's
  span tree shows the ``gap.preempt`` phase.
* **degradation** — an injected ``reqtrace.flush`` fault degrades to a
  counted ``trace_drops``; replies are untouched.
* **rates** — RateMeter rolling windows; ``stats`` sections carry them.
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import pytest

from music_analyst_tpu.serving.batcher import DynamicBatcher
from music_analyst_tpu.serving.slo import RateMeter
from music_analyst_tpu.telemetry.reqtrace import (
    PHASE_NAMES,
    configure_reqtrace,
    get_reqtrace,
    resolve_trace_sample,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _echo_ops(delay_s=0.0):
    def echo(texts):
        if delay_s:
            time.sleep(delay_s)
        return [{"text": t} for t in texts]

    return {"echo": echo}


@pytest.fixture
def traced(tmp_path):
    """A recorder flushing into ``tmp_path`` at sample 1.0; restores the
    disabled global (and the env the enable exported) afterwards."""
    recorder = configure_reqtrace(1.0, directory=str(tmp_path))
    yield tmp_path, recorder
    os.environ.pop("MUSICAAL_TRACE_DIR", None)
    os.environ.pop("MUSICAAL_TRACE_SAMPLE", None)
    configure_reqtrace(None, None)


def _records(tmp_path):
    path = tmp_path / "request_traces.jsonl"
    if not path.exists():
        return []
    return [json.loads(l) for l in path.read_text().splitlines() if l]


# ---------------------------------------------------------------- resolve


def test_resolve_trace_sample(monkeypatch):
    monkeypatch.delenv("MUSICAAL_TRACE_SAMPLE", raising=False)
    assert resolve_trace_sample(None) == 0.0
    assert resolve_trace_sample(0.25) == 0.25
    assert resolve_trace_sample("1.0") == 1.0
    monkeypatch.setenv("MUSICAAL_TRACE_SAMPLE", "0.5")
    assert resolve_trace_sample(None) == 0.5
    monkeypatch.setenv("MUSICAAL_TRACE_SAMPLE", "junk")
    assert resolve_trace_sample(None) == 0.0  # malformed env falls back
    monkeypatch.setenv("MUSICAAL_TRACE_SAMPLE", "7")
    assert resolve_trace_sample(None) == 0.0  # out-of-range env falls back
    with pytest.raises(ValueError):
        resolve_trace_sample("junk")  # explicit flag is a usage error
    with pytest.raises(ValueError):
        resolve_trace_sample(1.5)


def test_head_sampling_deterministic(traced):
    _, rt = traced
    ids = [os.urandom(8).hex() for _ in range(64)]
    rt.sample = 0.5
    first = [rt.sampled(i) for i in ids]
    assert first == [rt.sampled(i) for i in ids]  # same coin every call
    assert any(first) and not all(first)
    rt.sample = 0.0
    assert not any(rt.sampled(i) for i in ids)
    rt.sample = 1.0
    assert all(rt.sampled(i) for i in ids)


# ------------------------------------------------------- disabled = inert


def test_disabled_zero_wire_effect(tmp_path):
    assert not get_reqtrace().enabled  # the suite default
    b = DynamicBatcher(_echo_ops(), max_batch=4, max_wait_ms=1.0,
                       max_queue=8).start()
    try:
        reqs = [b.submit(i, "echo", f"t{i}") for i in range(4)]
        for r in reqs:
            assert r.wait(10.0)
        for r in reqs:
            assert "trace_id" not in r.response, r.response
            assert "trace" not in r.meta and "trace_t" not in r.meta
    finally:
        b.drain()
    assert not (tmp_path / "request_traces.jsonl").exists()


# -------------------------------------------------- tail keep: sheds fail


def test_sheds_carry_trace_ids_and_tail_flush(traced):
    tmp_path, rt = traced
    rt.sample = 0.0  # head sampling off: only the tail keep may flush
    b = DynamicBatcher(_echo_ops(delay_s=0.05), max_batch=2,
                       max_wait_ms=1.0, max_queue=2).start()
    try:
        reqs = [b.submit(i, "echo", f"t{i}") for i in range(12)]
        for r in reqs:
            assert r.wait(10.0)
    finally:
        b.drain()
    shed = [r for r in reqs if not r.response["ok"]]
    served = [r for r in reqs if r.response["ok"]]
    assert shed and served
    for r in reqs:  # every settle path stamps the id — sheds included
        assert isinstance(r.response.get("trace_id"), str), r.response
    for r in reqs:  # replay the reply-write seam the server owns
        rt.finish_request(r)
    records = _records(tmp_path)
    # Only the sheds flushed (tail keep); the healthy ones discarded.
    assert len(records) == len(shed)
    assert {r["kept"] for r in records} == {"queue_full"}
    assert {r["trace_id"] for r in records} == {
        r.response["trace_id"] for r in shed
    }
    stats = rt.stats()
    assert stats["tail_kept"] == len(shed)
    assert stats["discarded"] == len(served)


# ------------------------------------------------------ stdio end-to-end


def _subprocess_env(**overrides):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    env.pop("MUSICAAL_TRACE_DIR", None)
    env.pop("MUSICAAL_TRACE_SAMPLE", None)
    env.update(overrides)
    return env


def test_stdio_waterfall_trace_report_and_exemplars(tmp_path):
    """The acceptance waterfall: one traced generate request through the
    real stdio server — >=6 phases covering >=95% of wire latency, a
    0-exit trace-report, and exemplar ids that resolve to complete
    waterfalls."""
    requests = [
        {"id": "t1", "op": "generate", "text": "sunny morning",
         "max_new_tokens": 4},
        {"id": "t2", "op": "generate", "text": "rainy night",
         "max_new_tokens": 4},
    ]
    proc = subprocess.run(
        [sys.executable, "-m", "music_analyst_tpu", "serve", "--stdio",
         "--model", "llama-tiny", "--quiet", "--slots", "2",
         "--prefill-chunk", "32", "--max-new-tokens", "4",
         "--max-batch", "2", "--max-wait-ms", "2",
         "--trace-sample", "1.0", "--profile-dir", str(tmp_path),
         "--telemetry-dir", str(tmp_path)],
        input="".join(json.dumps(r) + "\n" for r in requests),
        capture_output=True, text=True, timeout=240,
        cwd=REPO, env=_subprocess_env(),
    )
    assert proc.returncode == 0, proc.stderr
    replies = {r["id"]: r
               for r in (json.loads(l) for l in proc.stdout.splitlines()
                         if l)}
    assert set(replies) == {"t1", "t2"}  # settle order may differ
    assert all(r["ok"] for r in replies.values())
    assert all(isinstance(r.get("trace_id"), str)
               for r in replies.values())

    records = _records(tmp_path)
    by_id = {r["trace_id"]: r for r in records}
    gen = by_id[replies["t1"]["trace_id"]]
    phases = [s for s in gen["spans"] if s["cat"] == "phase"]
    names = [s["name"] for s in phases]
    assert set(names) <= PHASE_NAMES
    assert len(phases) >= 6, names
    for expected in ("admit", "queue", "prefill", "decode", "commit",
                     "reply"):
        assert expected in names, names
    covered = sum(s["dur"] for s in phases)
    assert covered >= 0.95 * gen["wire_s"], (covered, gen["wire_s"])
    # Detail spans exist but never enter the attribution set.
    details = [s["name"] for s in gen["spans"] if s["cat"] == "detail"]
    assert "prefill.chunk" in details

    from music_analyst_tpu.observability.report import (
        build_trace_report,
        load_trace_records,
        run_trace_report,
    )

    assert run_trace_report([str(tmp_path)]) == 0
    report = build_trace_report(load_trace_records([str(tmp_path)]))
    assert report["n_complete"] == len(records)

    # Exemplar linkage: every quantile exemplar id in the manifest
    # resolves to a complete waterfall in request_traces.jsonl.
    manifest = json.loads((tmp_path / "run_manifest.json").read_text())
    exemplars = manifest["trace_exemplars"]["serving.request_seconds"]
    complete_ids = {
        t["trace_id"] for t in report["traces"] if t["complete"]
    }
    for quantile in ("p50", "p95", "p99"):
        assert exemplars[quantile]["trace_id"] in complete_ids
    assert manifest["reqtrace"]["flushed"] == len(records)
    # The rolling-rate satellite: serving sections carry window rates.
    assert manifest["serving"]["requests"]["rates"]["window_s"] == 10.0
    assert manifest["serving"]["decode"]["rates"]["req_s"] > 0.0
    # telemetry-report surfaces the exemplars next to the quantiles.
    from music_analyst_tpu.observability.report import build_report, load_run

    rec = load_run(str(tmp_path))
    rep = build_report([rec])
    blocks = [q for q in rep["latency_quantiles"]
              if q["name"] == "serving.request_seconds"]
    assert blocks and blocks[0]["exemplars"]["p99"]["trace_id"] in (
        complete_ids
    )


# -------------------------------------------------- preemption span tree


def test_preempted_resumed_span_tree(traced):
    """A preempted+resumed request's span tree shows the preemption gap
    (``gap.preempt``), tail-keeps with reason ``preempted``, keeps its
    cursor partition covering >=95% of wire latency — and tracing adds
    zero retraces while outputs stay byte-identical to untraced."""
    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )
    from music_analyst_tpu.serving.decode_loop import ContinuousScheduler

    tmp_path, rt = traced
    clf = LlamaZeroShotClassifier(config=LlamaConfig.tiny(),
                                  max_prompt_len=64)
    sched = ContinuousScheduler(
        clf, n_slots=1, prefill_chunk=16, prompt_region=64,
        max_new_tokens=8, max_queue=8, page_size=8, kv_pages=32,
        ttft_slo_ms=1.0,  # arm preemption; deadlines below stay generous
    )
    sched.warmup()

    def _staged(tag):
        low = sched.submit(f"low-{tag}", "slow burning ballad",
                           max_new_tokens=8, priority=1,
                           deadline_ms=60_000.0)
        for _ in range(32):
            sched._tick()
            slot = sched._slots[0]
            if slot is not None and slot.active and slot.steps > 0:
                break
        high = sched.submit(f"high-{tag}", "gold chorus mid decode",
                            max_new_tokens=8, priority=5,
                            deadline_ms=60_000.0)
        sched.run_until_idle()
        for req in (low, high):
            assert (req.response or {}).get("ok"), req.response
        return low, high

    # Untraced baseline on the same runtime (recorder off), then traced.
    # The enable exported env — pop it first or the re-resolve stays on.
    os.environ.pop("MUSICAAL_TRACE_DIR", None)
    os.environ.pop("MUSICAAL_TRACE_SAMPLE", None)
    configure_reqtrace(None, None)
    base_low, base_high = _staged("base")
    assert "trace_id" not in base_low.response
    rt = configure_reqtrace(1.0, directory=str(tmp_path))
    variants_before = sched.runtime.compiled_variants()
    low, high = _staged("traced")
    assert sched.runtime.compiled_variants() == variants_before  # no retrace
    assert low.response["text"] == base_low.response["text"]
    assert high.response["text"] == base_high.response["text"]
    assert sched.stats()["preemptions"] >= 2  # one per staged run

    for req in (low, high):
        rt.finish_request(req)
    records = {r["req_id"]: r for r in _records(tmp_path)}
    victim = records["low-traced"]
    # Tail-kept either way: the 1 ms SLO that arms preemption also marks
    # the victim's own TTFT miss, and keep() is first-reason-wins.
    assert victim["kept"] in ("preempted", "ttft_slo_miss")
    names = [s["name"] for s in victim["spans"] if s["cat"] == "phase"]
    assert "gap.preempt" in names
    # The interrupted phase is marked, and work resumes after the gap.
    preempted_spans = [
        s for s in victim["spans"]
        if (s.get("attrs") or {}).get("preempted")
    ]
    assert preempted_spans
    gap_i = names.index("gap.preempt")
    assert gap_i > 0 and gap_i < len(names) - 1  # work before AND after
    covered = sum(
        s["dur"] for s in victim["spans"] if s["cat"] == "phase"
    )
    assert covered >= 0.95 * victim["wire_s"]
    # The slot-stealing gold request flushed too (untouched by the gap).
    assert records["high-traced"]["wire_s"] > 0


# ------------------------------------------- cross-process fleet waterfall


def test_router_cross_process_waterfall(traced):
    """Two replica workers behind the router, one SIGKILLed mid-load:
    worker records parent-link to the front end's span, the front's
    ``downstream`` phase covers the worker round-trip, and any requeued
    request tail-keeps with a ``hop.requeue`` span."""
    from music_analyst_tpu.serving.router import (
        ReplicaRouter,
        spawn_replicas,
    )

    tmp_path, _ = traced
    rt = configure_reqtrace(1.0, directory=str(tmp_path), role="router")
    with tempfile.TemporaryDirectory() as base:
        handles = spawn_replicas(2, base, model="mock", mock=True,
                                 warmup=False, trace_sample=1.0)
        router = ReplicaRouter(handles, poll_interval_s=0.1).start()
        try:
            reqs = [router.submit(i, "sentiment", f"happy {i}")
                    for i in range(6)]
            os.kill(handles[0].proc.pid, signal.SIGKILL)
            reqs += [router.submit(6 + i, "sentiment", f"gray {i}")
                     for i in range(4)]
            for r in reqs:
                assert r.wait(60.0), f"request {r.id} never settled"
            for r in reqs:
                rt.finish_request(r)
            stats = router.stats()
        finally:
            router.drain()
    assert stats["rates"]["window_s"] == 10.0 and (
        stats["rates"]["req_s"] > 0.0
    )
    records = _records(tmp_path)
    fronts = [r for r in records if r["role"] == "router"]
    workers = [r for r in records if r["role"] == "server"]
    assert fronts and workers
    front_spans = {r["span"]: r for r in fronts}
    linked = [w for w in workers if w["parent"] in front_spans]
    assert linked, "no worker record parent-links to a front record"
    # Same trace id on both halves of a linked pair.
    for w in linked:
        assert front_spans[w["parent"]]["trace_id"] == w["trace_id"]
    ok_fronts = [
        r for r in fronts
        if "downstream" in [s["name"] for s in r["spans"]]
    ]
    assert ok_fronts, "no front record recorded a downstream phase"
    if stats["requeued"]:
        requeued = [
            r for r in fronts
            if "hop.requeue" in [s["name"] for s in r["spans"]]
        ]
        assert requeued and any(
            r["kept"] == "requeued" for r in requeued
        )


# --------------------------------------------------- flush fault degrades


def test_flush_fault_degrades_to_drops(traced):
    from music_analyst_tpu.resilience import configure_faults, fault_stats

    tmp_path, rt = traced
    b = DynamicBatcher(_echo_ops(), max_batch=4, max_wait_ms=1.0,
                       max_queue=8).start()
    configure_faults("reqtrace.flush:error@1+")
    try:
        reqs = [b.submit(i, "echo", f"t{i}") for i in range(4)]
        for r in reqs:
            assert r.wait(10.0)
            assert r.response["ok"]
            assert isinstance(r.response.get("trace_id"), str)
            rt.finish_request(r)  # the flush — and its fault — fires here
        trips = fault_stats()["reqtrace.flush"]["trips"]
    finally:
        configure_faults(None)
        b.drain()
    assert trips == 4
    stats = rt.stats()
    assert stats["trace_drops"] == 4 and stats["flushed"] == 0
    assert _records(tmp_path) == []  # no torn file, nothing half-written


# ----------------------------------------------------- trace-report gates


def test_trace_report_exit_codes(tmp_path, capsys):
    from music_analyst_tpu.observability.report import run_trace_report

    empty = tmp_path / "empty"
    empty.mkdir()
    assert run_trace_report([str(empty)]) == 2  # no usable input

    incomplete = {
        "schema": 1, "trace_id": "aa" * 8, "span": "1-1", "parent": None,
        "pid": 1, "role": "server", "req_id": "x", "op": "echo",
        "tenant": "default", "priority": 1, "kept": "head",
        "spans": [{"name": "admit", "cat": "phase", "t": 1.0,
                   "dur": 0.001}],
    }
    path = tmp_path / "request_traces.jsonl"
    path.write_text(json.dumps(incomplete) + "\n")
    assert run_trace_report([str(tmp_path)]) == 1  # traces, none complete

    complete = dict(incomplete, trace_id="bb" * 8, wire_s=0.01, spans=[
        {"name": "admit", "cat": "phase", "t": 1.0, "dur": 0.002},
        {"name": "queue", "cat": "phase", "t": 1.002, "dur": 0.002},
        {"name": "batch", "cat": "phase", "t": 1.004, "dur": 0.002},
        {"name": "commit", "cat": "phase", "t": 1.006, "dur": 0.002},
        {"name": "reply", "cat": "phase", "t": 1.008, "dur": 0.002},
    ])
    path.write_text(json.dumps(incomplete) + "\n"
                    + json.dumps(complete) + "\n")
    assert run_trace_report([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "attribution:" in out and "INCOMPLETE" in out
    assert run_trace_report([str(path)], json_output=True) == 0
    report = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert report["n_traces"] == 2 and report["n_complete"] == 1
    trace = [t for t in report["traces"] if t["complete"]][0]
    assert trace["coverage"] == 1.0
    assert set(trace["attribution"]) == {
        "admit", "queue", "batch", "commit", "reply"
    }


# ------------------------------------------------------------ rate meters


def test_rate_meter_rolls_and_decays():
    meter = RateMeter(tau_s=10.0)
    assert meter.rate() == 0.0
    for _ in range(5):
        meter.mark()
    assert 0.3 <= meter.rate() <= 0.51  # ~5 events / 10 s window
    fast = RateMeter(tau_s=0.05)
    fast.mark(10)
    r0 = fast.rate()
    time.sleep(0.2)
    assert fast.rate() < r0 / 10.0  # an idle meter forgets the burst


def test_batcher_stats_carry_rates():
    b = DynamicBatcher(_echo_ops(), max_batch=4, max_wait_ms=1.0,
                       max_queue=8).start()
    try:
        reqs = [b.submit(i, "echo", f"t{i}") for i in range(4)]
        for r in reqs:
            assert r.wait(10.0)
        rates = b.stats()["rates"]
        assert rates["window_s"] == 10.0
        assert rates["req_s"] > 0.0 and rates["shed_s"] == 0.0
    finally:
        b.drain()
