"""The process that holds the chip in a serve cell.

Does what ``python -m music_analyst_tpu serve --model <m> --socket <p>
--no-response-cache`` does (the same ``serving.server.run_server`` on the
main thread, the compile cache enabled the same way), and around it what
only the process that owns the device can do for the benchmark: the float32
reference on the sampled requests, the compile log, the device's memory
peak, and, when asked, a profiler trace of a few seconds started by the
parent touching ``trace.start``.  Writes ``child_setup.json`` before it
listens and ``child_report.json`` after the server has drained.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

import common  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--socket", required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--config", required=True)
    parser.add_argument("--sample", required=True)
    parser.add_argument("--chips", type=int, default=1)
    parser.add_argument("--trace-seconds", type=float, default=0.0)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args()
    config = common.load_json(args.config)

    devices = common.require_devices(args.chips, args.rehearsal)
    from music_analyst_tpu.telemetry import configure, get_telemetry
    from music_analyst_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )

    # as the CLI entry: telemetry on; its event log only in a traced run,
    # where the spans label the device's idle gaps
    configure(enabled=True,
              directory=args.out if args.trace_seconds else None)
    enable_persistent_compilation_cache()
    compiles = common.CompileLog()

    from music_analyst_tpu.engines.sentiment import get_backend
    from music_analyst_tpu.serving.server import run_server
    from reference import distilbert_f32

    t0 = time.monotonic()
    backend = get_backend(config["model"]["name"])
    backend_init_s = time.monotonic() - t0

    texts = common.load_json(args.sample)
    ids, lengths = backend.tokenizer.encode_batch(texts, backend.max_len)
    p_ref = distilbert_f32.positive_probability(
        backend.params, ids, lengths, config["n_layers"], config["n_heads"])
    with open(os.path.join(args.out, "child_setup.json"), "w") as fh:
        json.dump({
            "backend_init_s": backend_init_s,
            "p_ref": [float(p) for p in p_ref],
            "tolerance": distilbert_f32.TOLERANCE,
            "neutral_threshold": backend.neutral_threshold,
        }, fh)

    traced = {}
    stop_polling = threading.Event()

    def trace_when_asked() -> None:
        flag = os.path.join(args.out, "trace.start")
        while not os.path.exists(flag):
            if stop_polling.wait(0.02):
                return
        tracer = common.DeviceTrace(os.path.join(args.out, "trace"))
        tracer.start()
        with tracer.region():
            time.sleep(args.trace_seconds)
        traced["xplane"] = tracer.stop()

    thread = None
    if args.trace_seconds:
        thread = threading.Thread(target=trace_when_asked, daemon=True)
        thread.start()

    serve = config["fixed"].get("serve", {})
    rc = run_server(
        model=config["model"]["name"], backend=backend,
        socket_path=args.socket, use_response_cache=False, quiet=True,
        max_batch=serve.get("max_batch"), max_wait_ms=serve.get("max_wait_ms"),
        max_queue=serve.get("max_queue"),
    )
    stop_polling.set()
    if thread is not None:
        thread.join(timeout=60)

    tel = get_telemetry()
    report = {
        "rc": rc,
        "device": common.device_report(devices),
        "compiles": compiles.events,
        "histograms": {k: h.as_dict() for k, h in tel.histograms.items()},
        "trace": None,
    }
    if traced.get("xplane"):
        import trace_reduce

        spans = common.telemetry_spans(os.path.join(args.out, "telemetry.jsonl"))
        reduced = trace_reduce.reduce_file(
            traced["xplane"], spans, rehearsal=args.rehearsal)
        report["trace"] = reduced
    with open(os.path.join(args.out, "child_report.json"), "w") as fh:
        json.dump(report, fh)
    return rc


if __name__ == "__main__":
    sys.exit(main())
