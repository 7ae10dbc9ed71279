"""The serve knee: one server, a handful of fixed offered rates, each for
``--seconds``; prints for each rate the latency from the due time (p50,
p99), the share shed or unanswered, and whether the backlog grew (median
latency of the last quarter of the window against the first).  Run once on
the chip; the table goes into PERF.md and 0.8 of the knee into the traffic
file.  Not part of a run.

    python3 perfbench/tools/serve_sweep.py --rates 500,1000,1500,2000,3000 --seconds 10
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

import common  # noqa: E402
import loadgen  # noqa: E402
from drivers import open_loop_serve  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--rates", default="500,1000,1500,2000,3000")
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--traffic", default="serve_sentiment_steady")
    parser.add_argument("--config", default="distilbert-sst2")
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args()
    rates = [float(r) for r in args.rates.split(",")]
    traffic = common.load_json(os.path.join(
        BENCH_DIR, "traffic", args.traffic + ".json"))
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    # the driver's own set-up, sized for the highest rate; each rate then
    # takes its own stretch of the shuffled corpus (no lyric twice in a rate)
    traffic = dict(traffic, arrivals={
        "process": "poisson", "rate_rps": max(rates)}, rehearsal_arrivals={})
    config = common.load_json(os.path.join(
        BENCH_DIR, "configs", args.config + ".json"))
    if args.rehearsal:
        config = common.with_rehearsal_overrides(config)
    cell = {
        "name": "serve_sweep", "chips": 1, "seed": args.seed,
        "seconds": args.seconds, "trace": False, "rehearsal": args.rehearsal,
        "config": config,
        "traffic": traffic, "t_process": time.monotonic(),
        "out_dir": os.path.join(common.OUT_ROOT, "serve_sweep"),
    }
    state = open_loop_serve.setup(cell)
    state["client"].close()
    lines, used = state["lines"], 0
    try:
        for rate in rates:
            due = loadgen.poisson_times(rate, args.seconds, args.seed)
            due = due[:len(lines)]
            batch = [lines[(used + i) % len(lines)] for i in range(len(due))]
            used += len(due)
            before = json.loads(loadgen.call(
                state["socket"], b'{"id":"s","op":"stats"}\n'))["stats"]["requests"]
            client = loadgen.OpenLoopClient(
                state["socket"], traffic["connections"])
            outcome = client.run(batch, due)
            client.close()
            after = json.loads(loadgen.call(
                state["socket"], b'{"id":"s","op":"stats"}\n'))["stats"]["requests"]
            summary = loadgen.summarize(
                due, outcome["sent"], outcome["received"],
                traffic["latency_limit_ms"])
            latency = [(r - d) * 1e3 for r, d in zip(outcome["received"], due)
                       if r is not None]
            quarter = max(1, len(latency) // 4)
            not_ok = sum(1 for raw in outcome["raw"]
                         if not raw or not json.loads(raw).get("ok"))
            common.note(
                offered_rps=rate, requests=len(due),
                answered_rps=(len(due) - summary["unanswered"]) / args.seconds,
                p50_ms=summary["latency_p50_ms"], p99_ms=summary["latency_p99_ms"],
                met_limit_share=summary["met_limit_share"],
                not_ok_share=not_ok / max(1, len(due)),
                shed=after["shed"] - before["shed"],
                batches=after["batches"] - before["batches"],
                rows=after["rows"] - before["rows"],
                p50_first_quarter_ms=common.median(latency[:quarter]),
                p50_last_quarter_ms=common.median(latency[-quarter:]),
                lateness_median_ms=summary["lateness_median_ms"],
                lateness_max_ms=summary["lateness_max_ms"])
            time.sleep(1.0)
        loadgen.call(state["socket"], b'{"id":"x","op":"shutdown"}\n')
    finally:
        rc = open_loop_serve._stop_child(state, kill=state["child"].poll() is None
                                         and sys.exc_info()[0] is not None)
    report = common.load_json(os.path.join(state["out_dir"], "child_report.json"))
    common.note(child_rc=rc, device=report["device"],
                setup_s=state["setup_s"],
                compiles=len(report["compiles"]),
                compile_s=sum(d for _, d in report["compiles"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
