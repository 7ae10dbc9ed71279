"""Driver ``open_loop_serve``: one resident server, an open-loop client.

The parent (this file) never imports JAX: it starts ``serve_child.py``,
which holds the chip and runs the program's server on a unix socket, offers
load at the fixed rate of the traffic file from one thread, and afterwards
asks the server for its ``stats``, tells it to shut down and waits for the
process to end.  Each request is one ``sentiment`` op on one lyric of the
corpus, no lyric twice in a run.

Measures: ``latency_p50_ms``, ``latency_p99_ms`` (reply received minus the
time the request was due, over every request due in the window; unanswered
ranks last), ``requests_per_s`` (answered), ``setup_s``.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import time
from typing import Any, Dict

import common
import corpus
import loadgen


def setup(cell: Dict[str, Any]) -> Dict[str, Any]:
    config, traffic = cell["config"], cell["traffic"]
    out_dir = common.fresh_dir(os.path.join(cell["out_dir"], "run"))
    csv_path = corpus.ensure_corpus(
        common.OUT_ROOT, config["corpus"]["generator"], cell["seed"])
    texts = [row[3][:traffic["max_chars"]] for row in corpus.read_rows(csv_path)]
    random.Random(cell["seed"]).shuffle(texts)

    arrivals = dict(traffic["arrivals"])
    if cell["rehearsal"]:
        arrivals.update(traffic.get("rehearsal_arrivals", {}))
    due = loadgen.arrival_times(arrivals, cell["seconds"], cell["seed"])
    if len(due) > len(texts):
        raise SystemExit(
            f"perfbench: {len(due)} requests need more distinct lyrics than "
            f"the corpus's {len(texts)}")
    texts = texts[:len(due)]
    sample_n = min(config["model"]["reference_sample"], len(texts))
    sample_path = os.path.join(out_dir, "sample.json")
    with open(sample_path, "w", encoding="utf-8") as fh:
        json.dump(texts[:sample_n], fh)
    config_path = os.path.join(out_dir, "config.json")
    with open(config_path, "w", encoding="utf-8") as fh:
        json.dump(config, fh)

    socket_path = os.path.join(out_dir, "serve.sock")
    trace_s = traffic["trace_seconds"] if cell["trace"] else 0.0
    command = [
        sys.executable, os.path.join(common.BENCH_DIR, "serve_child.py"),
        "--socket", socket_path, "--out", out_dir, "--config", config_path,
        "--sample", sample_path, "--chips", str(cell["chips"]),
        "--trace-seconds", str(min(trace_s, cell["seconds"] / 2)),
    ] + (["--rehearsal"] if cell["rehearsal"] else [])
    err = open(os.path.join(out_dir, "child.err"), "wb")
    child = subprocess.Popen(command, stdout=err, stderr=err,
                             cwd=common.REPO_ROOT)
    state = {"cell": cell, "config": config, "traffic": traffic,
             "out_dir": out_dir, "child": child, "child_err": err,
             "socket": socket_path, "due": due, "texts": texts,
             "sample_n": sample_n, "trace_s": trace_s}
    try:
        _wait_ready(state)
    except BaseException:
        _stop_child(state, kill=True)
        raise
    state["lines"] = [
        (json.dumps({"id": i, "op": "sentiment", "text": text}) + "\n").encode()
        for i, text in enumerate(texts)
    ]
    state["client"] = loadgen.OpenLoopClient(socket_path, traffic["connections"])
    state["setup_s"] = time.monotonic() - cell["t_process"]
    state["child_setup"] = common.load_json(
        os.path.join(out_dir, "child_setup.json"))
    common.note(setup_s=state["setup_s"], requests=len(due),
                backend_init_s=state["child_setup"]["backend_init_s"])
    return state


def _wait_ready(state, timeout_s: float = 1150.0) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        rc = state["child"].poll()
        if rc is not None:
            # the child says why on its stderr; no device is its own code
            print(f"perfbench: the server process ended with {rc} before it "
                  f"listened; see {state['out_dir']}/child.err", file=sys.stderr)
            raise SystemExit(rc or 1)
        if os.path.exists(state["socket"]):
            try:
                reply = loadgen.call(state["socket"], b'{"id":"p","op":"ping"}\n')
                if json.loads(reply).get("ok"):
                    return
            except (OSError, ValueError):
                pass
        time.sleep(0.1)
    raise SystemExit("perfbench: the server did not listen in time")


def _stop_child(state, kill: bool = False) -> int:
    child = state["child"]
    if child.poll() is None:
        if kill:
            child.kill()
        try:
            child.wait(timeout=120)
        except subprocess.TimeoutExpired:
            child.kill()
            child.wait()
    state["child_err"].close()
    return child.returncode


def run(state: Dict[str, Any], seconds: float, trace: bool) -> Dict[str, Any]:
    cell, traffic, out_dir = state["cell"], state["traffic"], state["out_dir"]
    due, n = state["due"], len(state["due"])
    timers = []
    if trace:
        flag = os.path.join(out_dir, "trace.start")
        timers.append((max(0.0, (seconds - state["trace_s"]) / 2),
                       lambda: open(flag, "w").close()))
    try:
        t_window = time.monotonic()
        outcome = state["client"].run(state["lines"], due, timers=timers)
        t_end = time.monotonic()
        state["client"].close()
        stats = json.loads(loadgen.call(
            state["socket"], b'{"id":"s","op":"stats"}\n'))["stats"]
        loadgen.call(state["socket"], b'{"id":"x","op":"shutdown"}\n')
    except BaseException:
        _stop_child(state, kill=True)
        raise
    child_rc = _stop_child(state)
    report = common.load_json(os.path.join(out_dir, "child_report.json"))

    summary = loadgen.summarize(due, outcome["sent"], outcome["received"],
                                traffic["latency_limit_ms"])
    # --- correct ---------------------------------------------------------
    setup = state["child_setup"]
    tol, thr = setup["tolerance"], setup["neutral_threshold"]
    failed, labels = 0, [None] * n
    for i, raw in enumerate(outcome["raw"]):
        reply = json.loads(raw) if raw else {}
        if reply.get("id") != i or not reply.get("ok"):
            failed += 1
        else:
            labels[i] = reply.get("label")
    compared, wrong = 0, []
    for i, p in enumerate(setup["p_ref"]):
        want = common.expected_label(p, thr, tol)
        if want is None or not state["texts"][i].strip() or labels[i] is None:
            continue
        compared += 1
        if labels[i] != want:
            wrong.append(i)
    window_compiles = [e for e in report["compiles"]
                       if t_window <= e[0] <= t_end]
    late = summary["lateness_median_ms"]
    checks = {
        "answered_once_and_ok": failed == 0,
        "labels_compared": compared, "labels_wrong": wrong,
        "window_compiles": len(window_compiles),
        "generator_on_time": late is not None and late <= 1.0,
        "child_rc": child_rc,
    }
    correct = (failed == 0 and not wrong and not window_compiles
               and checks["generator_on_time"] and child_rc == 0)

    device = dict(report["device"])
    reduced = report.get("trace")
    breakdown = None
    if reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {
            "device_ops": [list(kv) for kv in reduced["device_ops"][:10]],
            "idle_gaps": [list(kv) for kv in reduced["idle_gaps"][:10]],
        }
    answered = n - summary["unanswered"]
    common.note(load=summary, checks=checks,
                server={k: stats["requests"].get(k) for k in (
                    "admitted", "shed", "completed", "failed", "batches",
                    "rows", "padded_rows", "queue_depth_max")})
    compile_events = [e for e in report["compiles"] if e[0] < t_window]
    return {
        "correct": correct, "attempted": n, "failed": failed,
        "device": device, "breakdown": breakdown,
        "measures": {
            "latency_p50_ms": summary["latency_p50_ms"],
            "latency_p99_ms": summary["latency_p99_ms"],
            "requests_per_s": answered / (t_end - t_window),
            "setup_s": state["setup_s"],
        },
        "artifacts": {
            "config": state["config"], "traffic": traffic,
            "chips": cell["chips"], "device": device, "trace": reduced,
            "setup": {"backend_init_s": setup["backend_init_s"],
                      "compile_s": sum(d for _, d in compile_events),
                      "compiles": len(compile_events),
                      "setup_s": state["setup_s"]},
            "window_compiles": len(window_compiles),
            "serve": {"stats": stats, "histograms": report["histograms"],
                      "load": summary},
        },
    }
