"""Process-crash drill: SIGKILL a live journaled server, restart, account.

Backs the "Crash recovery" section in PERFORMANCE.md.  Every other
resilience layer (retries, failover, drain, preemption) assumes the
process survives to run its recovery code; this suite drills the one
failure none of them can see — SIGKILL, the OOM killer, the pulled cord —
at each of the four named seams of the request path:

* ``serve.admit``     — post-admit, pre-dispatch (admission journaled,
  possibly not yet durable, no reply);
* ``serve.reply``     — pre-reply (the answer is computed but the crash
  eats it before the journal barrier and the wire);
* ``decode.step``     — mid-decode (a ``generate`` in flight on device);
* ``journal.compact`` — mid-compaction (fresh segment published, sealed
  history not yet unlinked).

Each drill spawns a real ``serve --stdio`` worker with ``--journal-dir``
and a ``MUSICAAL_FAULTS=<site>:crash@N`` rule, drives seeded loadgen
traffic (``benchmarks/loadgen.py``) into it until the injected SIGKILL
lands, then restarts a clean worker on the SAME journal directory and
re-sends every request id a real reconnecting client would retry.  The
acceptance bar, per drill:

* **100% accounting** — every offered request id gets an ok reply from
  the restarted server (journal replay or client-retry recompute; never
  silence);
* **zero duplicate computes** — every reply the client saw before the
  crash comes back byte-identical from the journal's dedup index
  (``deduped`` counts it; nothing re-executes);
* **unclean detection** — the restart stamps ``unclean_shutdown`` into
  its run manifest (the journal's missing ``clean`` marker is the
  witness; SIGKILL writes no flight record).

The suite also measures the journal's cost: the same in-process serving
run with and without a journal (batched admit fsyncs + group-committed
reply fsyncs), reported as ``overhead_pct`` against the ≤10% budget.
"""

from __future__ import annotations

import io
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks import suite
from benchmarks._util import (
    CPU_CHILDREN,
    clamped_timeout,
    device_info,
    smoke,
)
from benchmarks.loadgen import Arrival, LoadGen, poisson_arrivals

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Startup includes imports + model init (+ journal replay with compiles on
# the generative drill); clamped to the parent bench deadline at use.
_READY_CAP_S = 420.0
_SETTLE_CAP_S = 180.0

_MOCK_ARGS = ("--mock", "--no-warmup", "--max-batch", "8",
              "--max-wait-ms", "2")
_GEN_ARGS = ("--model", "llama3-tiny", "--no-warmup", "--slots", "2",
             "--max-new-tokens", "8")


class _WireReq:
    """LoadGen-compatible settleable handle for one NDJSON request."""

    def __init__(self, rid: Any) -> None:
        self.id = rid
        self.t_enqueue = time.monotonic()
        self.t_settle: Optional[float] = None
        self.response: Optional[Dict[str, Any]] = None
        self._event = threading.Event()

    @property
    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._event.wait(timeout)

    def settle(self, response: Dict[str, Any]) -> None:
        self.response = response
        self.t_settle = time.monotonic()
        self._event.set()


def _rid_key(rid: Any) -> str:
    try:
        return json.dumps(rid, sort_keys=True, separators=(",", ":"))
    except (TypeError, ValueError):
        return repr(rid)


class _ServerProc:
    """One ``serve --stdio`` incarnation plus its NDJSON client side.

    A SIGKILLed server closes our stdout pipe; the reader thread then
    settles every pending request as ``connection_lost`` so the drill
    (and LoadGen's settle loop) observes the crash instead of timing out.
    """

    def __init__(self, journal_dir: str, telemetry_dir: str, *,
                 faults: Optional[str], model_args: Sequence[str]) -> None:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("MUSICAAL_FAULTS", None)
        env.pop("MUSICAAL_SERVE_JOURNAL", None)
        if faults:
            env["MUSICAAL_FAULTS"] = faults
        self._stderr_path = os.path.join(telemetry_dir, "serve-stderr.log")
        self._stderr_fh = open(self._stderr_path, "w", encoding="utf-8")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "music_analyst_tpu", "serve", "--stdio",
             "--quiet", "--journal-dir", journal_dir,
             "--telemetry-dir", telemetry_dir, *model_args],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self._stderr_fh, text=True, cwd=_REPO, env=env,
        )
        self._lock = threading.Lock()
        self._pending: Dict[str, _WireReq] = {}
        self._dead = False
        self._reader = threading.Thread(
            target=self._read_loop, name="crash-bench-reader", daemon=True
        )
        self._reader.start()

    # ------------------------------------------------------------- client

    def _read_loop(self) -> None:
        try:
            for line in self.proc.stdout:
                line = line.strip()
                if not line:
                    continue
                try:
                    payload = json.loads(line)
                except ValueError:
                    continue
                with self._lock:
                    req = self._pending.pop(_rid_key(payload.get("id")),
                                            None)
                if req is not None:
                    req.settle(payload)
        except (OSError, ValueError):
            pass
        finally:
            self._dead = True
            self._fail_pending()

    def _fail_pending(self) -> None:
        with self._lock:
            stranded = list(self._pending.values())
            self._pending.clear()
        for req in stranded:
            req.settle({
                "id": req.id, "ok": False,
                "error": {"kind": "connection_lost",
                          "detail": "server process died mid-request"},
            })

    def request(self, rid: Any, payload: Dict[str, Any]) -> _WireReq:
        req = _WireReq(rid)
        if self._dead:
            req.settle({
                "id": rid, "ok": False,
                "error": {"kind": "connection_lost",
                          "detail": "server process already dead"},
            })
            return req
        with self._lock:
            self._pending[_rid_key(rid)] = req
        try:
            self.proc.stdin.write(json.dumps(dict(payload, id=rid)) + "\n")
            self.proc.stdin.flush()
        except (OSError, ValueError):
            with self._lock:
                self._pending.pop(_rid_key(rid), None)
            req.settle({
                "id": rid, "ok": False,
                "error": {"kind": "connection_lost",
                          "detail": "server died before the request "
                                    "was sent"},
            })
        return req

    def wait_ready(self, timeout_s: float) -> None:
        req = self.request("crash-bench-ready", {"op": "ping"})
        if not req.wait(timeout_s) or not (req.response or {}).get("ok"):
            raise RuntimeError(
                f"server never became ready: {self.tail_stderr()}"
            )

    # ---------------------------------------------------------- lifecycle

    def close_stdin(self) -> None:
        try:
            self.proc.stdin.close()
        except OSError:
            pass

    def wait(self, timeout_s: float) -> int:
        try:
            return self.proc.wait(timeout=timeout_s)
        finally:
            self._stderr_fh.close()

    def destroy(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
        for stream in (self.proc.stdin, self.proc.stdout):
            try:
                stream.close()
            except OSError:
                pass
        if not self._stderr_fh.closed:
            self._stderr_fh.close()

    def tail_stderr(self) -> str:
        try:
            with open(self._stderr_path, "r", encoding="utf-8") as fh:
                return fh.read()[-800:]
        except OSError:
            return "<no stderr captured>"


def _payload(arrival: Arrival) -> Dict[str, Any]:
    out: Dict[str, Any] = {
        "op": arrival.op, "text": arrival.text,
        "tenant": arrival.tenant, "priority": arrival.priority,
    }
    if arrival.max_new_tokens is not None:
        out["max_new_tokens"] = arrival.max_new_tokens
    return out


def _canon(response: Dict[str, Any]) -> str:
    return json.dumps(response, sort_keys=True, separators=(",", ":"))


def run_drill(name: str, fault_spec: str, base_dir: str, *,
              model_args: Sequence[str], trace: Sequence[Arrival],
              crash_on_close: bool = False) -> Dict[str, Any]:
    """One kill/restart cycle; importable so tests/test_journal.py can run
    a single seam without the whole suite."""
    journal_dir = os.path.join(base_dir, name, "journal")
    run1 = os.path.join(base_dir, name, "run1")
    run2 = os.path.join(base_dir, name, "run2")
    for directory in (journal_dir, run1, run2):
        os.makedirs(directory, exist_ok=True)
    start = time.perf_counter()

    # Phase 1: the crash incarnation — armed fault, live loadgen traffic.
    reqs1: List[Tuple[str, Dict[str, Any], _WireReq]] = []
    srv1 = _ServerProc(journal_dir, run1, faults=fault_spec,
                       model_args=model_args)
    try:
        srv1.wait_ready(clamped_timeout(_READY_CAP_S))

        def _submit(i: int, arrival: Arrival) -> _WireReq:
            rid = f"{name}-{i}"
            payload = _payload(arrival)
            req = srv1.request(rid, payload)
            reqs1.append((rid, payload, req))
            return req

        report1 = LoadGen(_submit).replay(
            trace, settle_timeout_s=clamped_timeout(_SETTLE_CAP_S)
        )
        if crash_on_close:
            # The kill point is inside the graceful-shutdown path itself:
            # EOF -> drain -> journal.close() -> compaction -> SIGKILL.
            srv1.close_stdin()
        rc1 = srv1.wait(clamped_timeout(_READY_CAP_S))
    finally:
        srv1.destroy()

    replied1 = {
        rid: req.response for rid, _, req in reqs1
        if (req.response or {}).get("ok")
    }
    lost1 = [rid for rid, _, req in reqs1
             if not (req.response or {}).get("ok")]

    # Phase 2: clean restart on the SAME journal; re-send every id like a
    # reconnecting client, then read the journal's own accounting.
    srv2 = _ServerProc(journal_dir, run2, faults=None,
                       model_args=model_args)
    try:
        srv2.wait_ready(clamped_timeout(_READY_CAP_S))
        reqs2 = [(rid, srv2.request(rid, payload))
                 for rid, payload, _ in reqs1]
        deadline = time.monotonic() + clamped_timeout(_SETTLE_CAP_S)
        for _, req in reqs2:
            req.wait(max(0.0, deadline - time.monotonic()))
        stats_req = srv2.request("crash-bench-stats", {"op": "stats"})
        stats_req.wait(clamped_timeout(60.0))
        journal_stats = ((stats_req.response or {}).get("stats") or {}).get(
            "journal") or {}
        srv2.close_stdin()
        rc2 = srv2.wait(clamped_timeout(_READY_CAP_S))
    finally:
        srv2.destroy()

    manifest: Dict[str, Any] = {}
    manifest_path = os.path.join(run2, "run_manifest.json")
    if os.path.exists(manifest_path):
        with open(manifest_path, "r", encoding="utf-8") as fh:
            manifest = json.load(fh)

    answered = {rid: req.response for rid, req in reqs2}
    all_accounted = bool(reqs1) and all(
        (answered.get(rid) or {}).get("ok") for rid, _, _ in reqs1
    )
    # Exactly-once at the wire: every reply the client saw in phase 1
    # must come back byte-identical from the dedup index.
    duplicates_identical = all(
        _canon(answered[rid]) == _canon(replied1[rid])
        for rid in replied1
    )
    deduped = int(journal_stats.get("deduped", 0))
    return {
        "scenario": name,
        "spec": fault_spec,
        "offered": len(trace),
        "submitted": len(reqs1),
        "replied_before_crash": len(replied1),
        "lost_in_crash": len(lost1),
        "loadgen_silent_drops": report1["silent_drops"],
        "killed_by_sigkill": rc1 == -signal.SIGKILL,
        "recovered_exit_ok": rc2 == 0,
        "all_accounted": all_accounted,
        "duplicates_deduped": duplicates_identical
        and deduped >= len(replied1),
        "unclean_stamped": manifest.get("unclean_shutdown") is True,
        "journal": {
            key: journal_stats.get(key)
            for key in ("replayed", "deduped", "corrupt_truncated",
                        "unclean_start", "open_requests")
        },
        "wall_s": round(time.perf_counter() - start, 3),
    }


def journal_overhead(n_mock: int, n_generate: int) -> Dict[str, Any]:
    """In-process serving wall time, journal off vs on (same traffic).

    Two looks at the same cost:

    * **mock** — a no-op backend, so the delta IS the journal's absolute
      per-request price (append + batched admit fsync + group-committed
      reply fsync), reported as ``per_request_ms``;
    * **generate** — real model work per request (the tiny decoder's
      continuous-batching path), so ``overhead_pct`` is the throughput
      cost a journaled production server actually pays — the ≤10%
      acceptance budget is judged here.
    """
    from music_analyst_tpu.models.mock import MockKeywordClassifier
    from music_analyst_tpu.serving.batcher import DynamicBatcher
    from music_analyst_tpu.serving.journal import RequestJournal
    from music_analyst_tpu.serving.server import SentimentServer, build_ops

    def _serve(lines: str, n: int, journal: Optional[RequestJournal],
               decode=None) -> float:
        batcher = DynamicBatcher(
            build_ops(MockKeywordClassifier()), max_batch=8,
            max_wait_ms=1.0, max_queue=n + 1,
        ).start()
        server = SentimentServer(batcher, mode="stdio", decode=decode,
                                 journal=journal)
        out = io.StringIO()
        t0 = time.perf_counter()
        # No drain on EOF: requests settle through the live batcher /
        # decode runtime, which stays reusable for the next pass.
        server.handle_stream(io.StringIO(lines), out)
        elapsed = time.perf_counter() - t0
        replies = [json.loads(line) for line in out.getvalue().splitlines()]
        if len(replies) != n or not all(r.get("ok") for r in replies):
            raise RuntimeError("journal-overhead run dropped replies")
        batcher.drain()
        return elapsed

    def _mock_lines(n: int, tag: str) -> str:
        return "".join(
            json.dumps({"id": f"{tag}-{i}", "op": "sentiment",
                        "text": f"sunshine and rain {tag} {i}"}) + "\n"
            for i in range(n)
        )

    def _gen_lines(n: int, tag: str) -> str:
        return "".join(
            json.dumps({"id": f"{tag}-{i}", "op": "generate",
                        "text": f"crash ballad {tag} number {i}",
                        "max_new_tokens": 4}) + "\n"
            for i in range(n)
        )

    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )
    from music_analyst_tpu.serving.decode_loop import ContinuousScheduler

    clf = LlamaZeroShotClassifier(
        config=LlamaConfig.tiny(), max_prompt_len=64
    )
    sched = ContinuousScheduler(
        clf, n_slots=2, prefill_chunk=16, prompt_region=64,
        max_new_tokens=8, max_queue=n_generate + 1,
    )
    sched.warmup()
    sched.start()
    try:
        with tempfile.TemporaryDirectory(prefix="crash_overhead_") as tmp:
            _serve(_mock_lines(n_mock, "warm"), n_mock, None)
            mock_bare_s = _serve(_mock_lines(n_mock, "bare"), n_mock, None)
            journal = RequestJournal(os.path.join(tmp, "wal-mock"))
            journal.recover()
            mock_journaled_s = _serve(
                _mock_lines(n_mock, "wal"), n_mock, journal
            )
            journal.close()

            # Distinct prompts per pass (same shapes) so the paged radix
            # cache can't hand the journaled pass a warm-prefix discount.
            _serve(_gen_lines(n_generate, "warm"), n_generate, None,
                   decode=sched)
            gen_bare_s = _serve(_gen_lines(n_generate, "bare"), n_generate,
                                None, decode=sched)
            journal = RequestJournal(os.path.join(tmp, "wal-gen"))
            journal.recover()
            gen_journaled_s = _serve(
                _gen_lines(n_generate, "wal"), n_generate, journal,
                decode=sched,
            )
            journal.close()
    finally:
        sched.drain()
    overhead_pct = (gen_journaled_s - gen_bare_s) / gen_bare_s * 100.0
    return {
        "mock_requests": n_mock,
        "mock_bare_wall_s": round(mock_bare_s, 4),
        "mock_journaled_wall_s": round(mock_journaled_s, 4),
        "per_request_ms": round(
            (mock_journaled_s - mock_bare_s) / n_mock * 1000.0, 4
        ),
        "generate_requests": n_generate,
        "generate_bare_wall_s": round(gen_bare_s, 4),
        "generate_journaled_wall_s": round(gen_journaled_s, 4),
        "overhead_pct": round(overhead_pct, 2),
        "within_budget": overhead_pct <= 10.0,
    }


def _mock_trace(n: int, seed: int) -> List[Arrival]:
    classes = [
        {"op": "sentiment", "tenant": "bulk", "weight": 2.0},
        {"op": "wordcount", "tenant": "gold", "priority": 3},
    ]
    # Bursty on purpose: back-to-back admits make the fsync batching and
    # the admit/reply interleave around the kill point interesting.
    return poisson_arrivals(400.0, n / 40.0, seed=seed,
                            classes=classes)[:n]


def _gen_trace(n: int, seed: int) -> List[Arrival]:
    classes = [{"op": "generate", "max_new_tokens": 4}]
    return poisson_arrivals(20.0, n, seed=seed, classes=classes)[:n]


@suite("crash")
def run() -> dict:
    n_mock = 10 if smoke() else 32
    n_gen = 3 if smoke() else 8
    rows = []
    with tempfile.TemporaryDirectory(prefix="crash_bench_") as base:
        for name, spec, model_args, trace, on_close in (
            ("post_admit", "serve.admit:crash@3", _MOCK_ARGS,
             _mock_trace(n_mock, seed=11), False),
            # The readiness ping is reply #1, so @4 kills the server just
            # before the third *request* reply reaches the wire.
            ("pre_reply", "serve.reply:crash@4", _MOCK_ARGS,
             _mock_trace(n_mock, seed=13), False),
            ("mid_decode", "decode.step:crash@3", _GEN_ARGS,
             _gen_trace(n_gen, seed=17), False),
            ("mid_compaction", "journal.compact:crash@1", _MOCK_ARGS,
             _mock_trace(max(4, n_mock // 2), seed=19), True),
        ):
            row = run_drill(name, spec, base, model_args=model_args,
                            trace=trace, crash_on_close=on_close)
            rows.append(row)
            print(
                f"[crash] {name}: killed={row['killed_by_sigkill']} "
                f"accounted={row['all_accounted']} "
                f"deduped={row['journal']['deduped']} "
                f"replayed={row['journal']['replayed']} "
                f"wall={row['wall_s']:.1f}s",
                file=sys.stderr,
            )

    overhead = journal_overhead(
        256 if smoke() else 2048, 8 if smoke() else 32
    )
    print(
        f"[crash] journal overhead: {overhead['per_request_ms']:.2f} "
        f"ms/request (mock), {overhead['overhead_pct']:+.1f}% on the "
        f"generative path "
        f"({overhead['generate_bare_wall_s']:.3f}s -> "
        f"{overhead['generate_journaled_wall_s']:.3f}s)",
        file=sys.stderr,
    )

    return {
        "suite": "crash",
        "device": device_info(),  # the in-process journal-overhead rows
        "drill_servers": CPU_CHILDREN,
        "smoke": smoke(),
        "drills": rows,
        "journal_overhead": overhead,
        "all_killed": all(r["killed_by_sigkill"] for r in rows),
        "all_recovered": all(r["recovered_exit_ok"] for r in rows),
        "all_accounted": all(
            r["all_accounted"] and r["loadgen_silent_drops"] == 0
            for r in rows
        ),
        "zero_duplicate_computes": all(
            r["duplicates_deduped"] for r in rows
        ),
        "all_unclean_stamped": all(r["unclean_stamped"] for r in rows),
    }
