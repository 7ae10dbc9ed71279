"""Resilience layer: fault injection, retry policy, failover, atomicity.

The chaos contracts (ISSUE 9): every injected transient fault either
recovers with byte-identical artifacts (retries visible in telemetry)
or — for non-transient injection — fails with a structured taxonomy
error and no torn files.  Degrade paths honor the golden contracts
(``word_counts.csv`` byte-stable) too.
"""

import json
import os

import pytest

from music_analyst_tpu.resilience import (
    InjectedFatal,
    InjectedFault,
    RetryPolicy,
    arm_retry_deadline,
    classify_retryable,
    configure_faults,
    fault_point,
    fault_stats,
    parse_fault_spec,
    reset_retry_stats,
    resolve_fault_spec,
    resolve_http_retries,
    retry_stats,
    run_with_failover,
    should_failover,
)
from music_analyst_tpu.resilience.faults import FaultRule

FIXTURE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fixtures", "mini_songs.csv"
)


@pytest.fixture(autouse=True)
def _pristine_resilience():
    """Every test starts and ends with no injector, stats, or deadline."""
    configure_faults(None)
    reset_retry_stats()
    arm_retry_deadline(None)
    yield
    configure_faults(None)
    reset_retry_stats()
    arm_retry_deadline(None)


def _zero_sleep_policy(**kwargs):
    return RetryPolicy(sleep=lambda s: None, **kwargs)


# ------------------------------------------------------------ spec parsing


def test_parse_full_grammar():
    rules = parse_fault_spec(
        "ollama.request:error@2;h2d.transfer:delay=5s@0.1%seed=7;"
        "ingest.read:fatal;prefetch.stage:error@3+"
    )
    by_site = {r.site: r for r in rules}
    assert by_site["ollama.request"].mode == "error"
    assert by_site["ollama.request"].nth == 2
    assert not by_site["ollama.request"].from_nth
    assert by_site["h2d.transfer"].mode == "delay"
    assert by_site["h2d.transfer"].delay_s == 5.0
    assert by_site["h2d.transfer"].probability == pytest.approx(0.001)
    assert by_site["h2d.transfer"].seed == 7
    assert by_site["ingest.read"].mode == "fatal"
    assert by_site["ingest.read"].nth is None
    assert by_site["prefetch.stage"].nth == 3
    assert by_site["prefetch.stage"].from_nth


@pytest.mark.parametrize("bad", [
    "nonsense.site:error",          # unknown site
    "ingest.read",                  # no mode
    "ingest.read:explode",          # unknown mode
    "ingest.read:error@zero",       # non-numeric trigger
    "ingest.read:error@0",          # calls are 1-based
    "ingest.read:delay=oops",       # bad delay
    "ingest.read:delay=9999s",      # above the sleep cap
    "ingest.read:error@150%",       # probability out of range
    "ingest.read:error@1seed=x",    # bad seed
    "; ;",                          # no rules at all
])
def test_parse_rejects_garbage(bad):
    with pytest.raises(ValueError):
        parse_fault_spec(bad)


def test_resolve_spec_explicit_beats_env(monkeypatch):
    monkeypatch.setenv("MUSICAAL_FAULTS", "ingest.read:error")
    assert resolve_fault_spec("ollama.request:error") == "ollama.request:error"
    assert resolve_fault_spec(None) == "ingest.read:error"
    monkeypatch.delenv("MUSICAAL_FAULTS")
    assert resolve_fault_spec(None) is None


def test_bad_env_spec_raises_loudly(monkeypatch):
    """Unlike the watchdog env knob, a garbage MUSICAAL_FAULTS raises —
    a chaos run silently testing nothing would be worse than crashing."""
    monkeypatch.setenv("MUSICAAL_FAULTS", "not-a-site:error")
    with pytest.raises(ValueError, match="unknown site"):
        configure_faults(resolve_fault_spec(None))


# ------------------------------------------------------- seeded determinism


def test_probabilistic_schedule_is_seed_deterministic():
    def schedule(seed):
        rule = FaultRule(site="ingest.read", mode="error",
                        probability=0.3, seed=seed)
        return [rule.should_trip(i) for i in range(1, 201)]

    assert schedule(7) == schedule(7)
    assert any(schedule(7))  # 0.3 over 200 draws trips w.p. ~1
    assert schedule(7) != schedule(8)


def test_injected_run_schedule_replays():
    """Same spec, fresh injector → identical trip schedule at the seam."""
    def trips(spec):
        configure_faults(spec)
        out = []
        for _ in range(50):
            try:
                fault_point("ingest.read")
                out.append(False)
            except InjectedFault:
                out.append(True)
        return out

    spec = "ingest.read:error@25%seed=3"
    first = trips(spec)
    assert first == trips(spec)
    assert any(first) and not all(first)


def test_nth_and_from_nth_triggers():
    configure_faults("ingest.read:error@2")
    fault_point("ingest.read")  # call 1: clean
    with pytest.raises(InjectedFault, match=r"call 2"):
        fault_point("ingest.read")
    fault_point("ingest.read")  # call 3: clean again
    assert fault_stats()["ingest.read"] == {
        "rules": [{"site": "ingest.read", "mode": "error", "nth": 2}],
        "calls": 3,
        "trips": 1,
    }

    configure_faults("ingest.read:error@2+")
    fault_point("ingest.read")
    for _ in range(3):  # every call from the 2nd on
        with pytest.raises(InjectedFault):
            fault_point("ingest.read")


def test_fatal_is_not_retryable():
    configure_faults("ingest.read:fatal")
    with pytest.raises(InjectedFatal) as exc_info:
        fault_point("ingest.read")
    retryable, kind = classify_retryable(exc_info.value)
    assert (retryable, kind) == (False, "fault_injected")
    # ...while a plain error is.
    assert classify_retryable(InjectedFault("ingest.read", 1)) == (
        True, "fault_injected"
    )


def test_fault_kind_matches_report_taxonomy():
    from music_analyst_tpu.observability.report import classify_error

    assert classify_error(str(InjectedFault("h2d.transfer", 3))) == (
        "fault_injected"
    )


# ------------------------------------------------------------- retry policy


def test_retry_recovers_and_counts():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise InjectedFault("ingest.read", calls["n"])
        return "ok"

    policy = _zero_sleep_policy(retries=2)
    assert policy.call(flaky, site="unit.flaky") == "ok"
    stats = retry_stats()["unit.flaky"]
    assert stats == {"attempts": 3, "retries": 2,
                     "recoveries": 1, "gave_up": 0}


def test_non_retryable_raises_on_first_attempt():
    calls = {"n": 0}

    def broken():
        calls["n"] += 1
        raise ValueError("logic error")

    with pytest.raises(ValueError):
        _zero_sleep_policy(retries=5).call(broken, site="unit.broken")
    assert calls["n"] == 1
    assert "gave_up" not in {
        k: v for k, v in retry_stats()["unit.broken"].items() if v
    }


def test_exhausted_retries_reraise_last_error():
    def always_down():
        raise ConnectionError("refused")

    with pytest.raises(ConnectionError):
        _zero_sleep_policy(retries=2).call(always_down, site="unit.down")
    stats = retry_stats()["unit.down"]
    assert stats["attempts"] == 3 and stats["gave_up"] == 1


def test_deadline_forbids_sleeping_past_budget():
    """With no budget left the policy re-raises NOW instead of sleeping —
    the structured error line must beat the bench deadline."""
    arm_retry_deadline(0.0)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        raise InjectedFault("ingest.read", calls["n"])

    policy = RetryPolicy(retries=5, base_s=0.5, cap_s=2.0)
    with pytest.raises(InjectedFault):
        policy.call(flaky, site="unit.deadline")
    assert calls["n"] == 1  # never slept, never re-attempted
    assert retry_stats()["unit.deadline"]["gave_up"] == 1


def test_backoff_respects_cap():
    policy = RetryPolicy(base_s=10.0, cap_s=0.5)
    assert all(policy.backoff_s(attempt) <= 0.5 for attempt in range(1, 8))


def test_resolve_http_retries_validation(monkeypatch):
    assert resolve_http_retries(None, default=2) == 2
    assert resolve_http_retries("5") == 5
    monkeypatch.setenv("MUSICAAL_HTTP_RETRIES", "3")
    assert resolve_http_retries(None) == 3
    monkeypatch.setenv("MUSICAAL_HTTP_RETRIES", "lots")
    with pytest.raises(ValueError, match="MUSICAAL_HTTP_RETRIES"):
        resolve_http_retries(None)
    with pytest.raises(ValueError, match="-1"):
        resolve_http_retries(-1)


# ----------------------------------------------------------------- failover


def test_failover_reinit_then_recover():
    state = {"healthy": False, "reinits": 0}

    def compute():
        if not state["healthy"]:
            raise InjectedFault("collective.psum", 1)
        return 42

    def reinit():
        state["reinits"] += 1
        state["healthy"] = True

    result = run_with_failover(
        compute, site="unit.failover", reinit=reinit
    )
    assert result == 42
    assert state["reinits"] == 1


def test_failover_second_failure_propagates():
    """One re-init-and-retry, then the error — there is no host path to
    finish on."""
    calls = {"n": 0}

    def compute():
        calls["n"] += 1
        raise RuntimeError("backend lost: device went away")

    with pytest.raises(RuntimeError, match="backend lost"):
        run_with_failover(compute, site="unit.lost")
    assert calls["n"] == 2


def test_failover_ignores_logic_errors():
    def compute():
        raise KeyError("missing column")

    with pytest.raises(KeyError):
        run_with_failover(compute, site="unit.logic")
    assert not should_failover(KeyError("x"))
    assert should_failover(InjectedFault("collective.psum", 1))


# -------------------------------------------------------- prefetch seam


def test_prefetch_stage_retry_then_succeed():
    from music_analyst_tpu.runtime.prefetch import PrefetchPipeline, Stage

    configure_faults("prefetch.stage:error@2")
    pipe = PrefetchPipeline(
        [Stage("double", lambda x: x * 2)], depth=2, name="unit_pipe"
    )
    assert list(pipe.run(range(5))) == [0, 2, 4, 6, 8]
    assert fault_stats()["prefetch.stage"]["trips"] == 1
    assert retry_stats()["prefetch.stage"]["recoveries"] == 1


# ------------------------------------------------- engine-level chaos runs


def _word_counts_bytes(out_dir):
    with open(os.path.join(out_dir, "word_counts.csv"), "rb") as fh:
        return fh.read()


def test_wordcount_transient_ingest_fault_byte_identical(tmp_path):
    from music_analyst_tpu.engines.wordcount import run_analysis

    clean = tmp_path / "clean"
    faulted = tmp_path / "faulted"
    run_analysis(FIXTURE, output_dir=str(clean), write_split=False,
                 quiet=True, use_corpus_cache=False)
    configure_faults("ingest.read:error@1")
    run_analysis(FIXTURE, output_dir=str(faulted), write_split=False,
                 quiet=True, use_corpus_cache=False)
    assert _word_counts_bytes(clean) == _word_counts_bytes(faulted)
    assert retry_stats()["ingest.read"]["recoveries"] == 1
    manifest = json.loads((faulted / "run_manifest.json").read_text())
    assert manifest["resilience"]["faults"]["ingest.read"]["trips"] == 1
    assert manifest["counters"]["retry.ingest.read.recovered"] == 1


def test_wordcount_persistent_device_fault_fails_the_run(tmp_path):
    """Persistent device-path failure → one failover retry, then the run
    FAILS (non-zero from the CLI): no host-side count path, no
    ``degraded`` stamp, no torn artifacts."""
    from music_analyst_tpu.cli.main import main

    out = tmp_path / "lost"
    with pytest.raises(InjectedFault):
        main([
            "analyze", FIXTURE, "--output-dir", str(out), "--no-split",
            "--no-corpus-cache", "--inject-faults", "collective.psum:error",
        ])
    leftovers = [
        name for name in os.listdir(out)
        if name.endswith(".csv") or ".tmp-" in name
    ]
    assert leftovers == []
    manifest = json.loads((out / "run_manifest.json").read_text())
    assert "degraded" not in manifest
    counters = manifest["counters"]
    assert counters["failover.wordcount.device_compute.retries"] == 1
    assert "failover.wordcount.device_compute.recoveries" not in counters


def test_fatal_injection_dies_structurally_no_torn_files(tmp_path):
    from music_analyst_tpu.engines.wordcount import run_analysis
    from music_analyst_tpu.observability.report import classify_error

    out = tmp_path / "fatal"
    configure_faults("ingest.read:fatal")
    with pytest.raises(InjectedFatal) as exc_info:
        run_analysis(FIXTURE, output_dir=str(out), write_split=False,
                     quiet=True, use_corpus_cache=False)
    assert classify_error(str(exc_info.value)) == "fault_injected"
    # No torn artifacts: the atomic writers never leave partial CSVs or
    # stray tmp files behind a failed run.
    leftovers = [
        name for name in os.listdir(out)
        if name.endswith(".csv") or ".tmp-" in name
    ] if out.exists() else []
    assert leftovers == []


def test_sentiment_mock_h2d_fault_byte_identical(tmp_path):
    from music_analyst_tpu.engines.sentiment import run_sentiment

    clean = tmp_path / "clean"
    faulted = tmp_path / "faulted"
    run_sentiment(FIXTURE, mock=True, output_dir=str(clean), quiet=True)
    configure_faults("h2d.transfer:error@1")
    run_sentiment(FIXTURE, mock=True, output_dir=str(faulted), quiet=True)
    for name in ("sentiment_details.csv", "sentiment_totals.json"):
        assert (clean / name).read_bytes() == (faulted / name).read_bytes()
    assert retry_stats()["prefetch.stage"]["recoveries"] >= 1


# ----------------------------------------------------------- serving seam


def test_serving_dispatch_retry_answers_everyone():
    from music_analyst_tpu.serving.batcher import DynamicBatcher

    configure_faults("serving.dispatch:error@1")
    ops = {"echo": lambda texts: [{"label": t} for t in texts]}
    batcher = DynamicBatcher(ops, max_batch=4, max_wait_ms=1.0,
                             max_queue=64).start()
    reqs = [batcher.submit(i, "echo", f"row {i}") for i in range(16)]
    for req in reqs:
        assert req.wait(timeout=30.0)
        assert req.response["ok"], req.response
    batcher.drain()
    assert retry_stats()["serving.dispatch"]["recoveries"] == 1


def test_residency_reload_swaps_poisoned_backend_mid_session():
    """Reload-on-poisoned-device: a backend that dies with a classified
    backend-loss error is replaced under the live batcher; the request that hit
    it still gets an answer from the fresh backend."""
    from music_analyst_tpu.serving.batcher import DynamicBatcher
    from music_analyst_tpu.models.backend import ModelResidency
    from music_analyst_tpu.serving.server import build_resident_ops

    class PoisonedBackend:
        name = "poisoned"

        def classify_batch(self, texts):
            raise ConnectionError("backend lost: device went away")

    residency = ModelResidency(model="mock", mock=True,
                               backend=PoisonedBackend())
    batcher = DynamicBatcher(
        build_resident_ops(residency),
        max_batch=4, max_wait_ms=1.0, max_queue=16,
        failover=lambda exc: residency.reload() is not None,
    ).start()
    req = batcher.submit("r1", "sentiment", "I love this happy day")
    assert req.wait(timeout=30.0)
    batcher.drain()
    assert req.response["ok"], req.response
    assert residency.snapshot()["reloads"] == 1
    assert batcher.stats()["failover_reloads"] == 1


# -------------------------------------------------------- flight recording


def test_flight_record_contains_injected_fault_events(tmp_path):
    from music_analyst_tpu.observability.flight import FlightRecorder

    rec = FlightRecorder()
    rec.install(signals=False, excepthook=False)
    try:
        configure_faults("ingest.read:error@1")
        with pytest.raises(InjectedFault):
            fault_point("ingest.read", path="unit.csv")
        path = rec.dump("unit-test", taxonomy="fault_injected",
                        directory=str(tmp_path))
    finally:
        rec.uninstall()
    record = json.loads(open(path, encoding="utf-8").read())
    faults = [e for e in record["events"]
              if e.get("name") == "fault_injected"]
    assert faults, "flight record lost the injected-fault event"
    assert faults[0]["attrs"]["site"] == "ingest.read"
    assert faults[0]["attrs"]["path"] == "unit.csv"


# -------------------------------------------------------- atomic artifacts


def test_atomic_write_replaces_only_on_success(tmp_path):
    from music_analyst_tpu.utils.atomic import atomic_write

    target = tmp_path / "out.csv"
    target.write_text("original")
    with pytest.raises(RuntimeError):
        with atomic_write(str(target)) as fh:
            fh.write("half a row")
            raise RuntimeError("crash mid-write")
    assert target.read_text() == "original"  # untouched
    assert [n for n in os.listdir(tmp_path) if ".tmp-" in n] == []
    with atomic_write(str(target)) as fh:
        fh.write("replaced")
    assert target.read_text() == "replaced"


def test_wq_cache_publish_retries_transient_rename(tmp_path):
    from music_analyst_tpu.engines.wq_cache import WqCacheWriter
    import numpy as np

    configure_faults("corpus_cache.publish:error@1")
    writer = WqCacheWriter(str(tmp_path), "entry")
    writer.add("layer/kernel", np.ones((2, 2), dtype=np.float32))
    assert writer.publish() is True  # retry absorbed the injected rename
    assert (tmp_path / "entry").is_dir()
    assert retry_stats()["corpus_cache.publish"]["recoveries"] == 1
