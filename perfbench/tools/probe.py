"""One-off measurements on the chip that fix numbers the benchmark's files
carry: rows per chip (``memory_stats()`` whole and songs/s against batch
rows: the allocator's peak does not count a running program's scratch), the
reference tolerance (bfloat16 and int8 forwards against the float32
reference), and a first look at a device trace.  Not part of a run.

    python3 perfbench/tools/probe.py --rows 4096,8192,16384 --int8 1
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

import numpy as np  # noqa: E402

import common  # noqa: E402
import corpus  # noqa: E402
import trace_reduce  # noqa: E402


def positive(backend, texts):
    handle = backend.launch(backend.transfer(backend.prepare(texts)))
    (_, classes, confidence, _), = handle[1]
    classes = np.asarray(classes)[:len(texts)]
    confidence = np.asarray(confidence)[:len(texts)].astype(np.float64)
    return np.where(classes == 1, confidence, 1.0 - confidence)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", default="4096,8192,16384,32768")
    parser.add_argument("--int8", type=int, default=1)
    parser.add_argument("--out", default=os.path.join(
        common.REPO_ROOT, "chiprun_out", "probe"))
    args = parser.parse_args()
    os.makedirs(args.out, exist_ok=True)

    import jax

    from music_analyst_tpu.engines.sentiment import get_backend
    from music_analyst_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )
    from reference import distilbert_f32

    devices = common.require_devices(1, False)
    enable_persistent_compilation_cache()
    config = common.load_json(os.path.join(
        BENCH_DIR, "configs", "distilbert-sst2.json"))
    csv_path = corpus.ensure_corpus(
        common.OUT_ROOT, config["corpus"]["generator"], 1)
    texts = [row[3] for row in corpus.read_rows(csv_path)]
    t0 = time.monotonic()
    backend = get_backend("distilbert")
    common.note(backend_init_s=time.monotonic() - t0)

    # 1. tolerance: bf16 (the system) and int8 against the f32 reference
    sample = np.sort(np.random.default_rng(7).choice(4096, 64, replace=False))
    p_sys = positive(backend, texts[:4096])[sample]
    ids, lengths = backend.tokenizer.encode_batch(
        [texts[i] for i in sample], backend.max_len)
    p_ref = distilbert_f32.positive_probability(
        backend.params, ids, lengths, config["n_layers"], config["n_heads"])
    d = np.abs(p_sys - p_ref)
    common.note(check="bf16_vs_f32", max=float(d.max()),
                median=float(np.median(d)), p_ref_min=float(p_ref.min()),
                p_ref_max=float(p_ref.max()))
    common.note(rows=4096, memory_stats=devices[0].memory_stats())

    # 2. a device trace of three steps, described, and kept
    trace_dir = os.path.join(args.out, "trace_steps")
    shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = common.DeviceTrace(trace_dir)
    tracer.start()
    with tracer.region():
        for _ in range(3):
            t = time.monotonic()
            backend.classify_batch(texts[:4096])
            common.note(step_s=time.monotonic() - t)
            time.sleep(0.05)
    xplane = tracer.stop()
    with open(os.path.join(args.out, "trace_steps.txt"), "w") as fh:
        fh.write("\n".join(trace_reduce.describe_file(xplane, 6)))
    reduced = trace_reduce.reduce_file(xplane)
    common.note(trace_bytes=os.path.getsize(xplane),
                window_s=reduced["window_s"], busy_s=reduced["busy_s"],
                ops=reduced["device_ops"][:8],
                modules={k: (len(v), float(np.median(v))) for k, v in
                         reduced["devices"][sorted(reduced["devices"])[0]]
                         ["module_runs_s"].items()})

    # 3. a small trace with known structure, for the reduction's test
    import jax.numpy as jnp

    small = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    small(x).block_until_ready()
    small_dir = os.path.join(args.out, "trace_small")
    shutil.rmtree(small_dir, ignore_errors=True)
    tracer = common.DeviceTrace(small_dir)
    tracer.start()
    spans = common.HostSpans()
    with tracer.region():
        for i in range(4):
            with spans.span("step"):
                small(x).block_until_ready()
            with spans.span("sleep"):
                time.sleep(0.02)
    small_plane = tracer.stop()
    shutil.copy(small_plane, os.path.join(args.out, "small.xplane.pb"))
    with open(os.path.join(args.out, "small_spans.json"), "w") as fh:
        import json
        json.dump(spans.spans, fh)
    common.note(small_trace_bytes=os.path.getsize(small_plane))

    # 4. the int8 forward against the same reference
    if args.int8:
        int8 = get_backend("distilbert-int8")
        p_int8 = positive(int8, texts[:4096])[sample]
        d8 = np.abs(p_int8 - p_ref)
        common.note(check="int8_vs_f32", max=float(d8.max()),
                    median=float(np.median(d8)))
    # 5. rows per chip: seconds a batch, songs/s and peak memory
    for rows in [int(r) for r in args.rows.split(",")]:
        batch = (texts * (1 + rows // len(texts)))[:rows]
        times = []
        try:
            for _ in range(4):
                t = time.monotonic()
                backend.classify_batch(batch)
                times.append(time.monotonic() - t)
        except Exception as exc:  # noqa: BLE001  an OOM ends the sweep
            common.note(rows=rows, error=str(exc)[:300])
            break
        common.note(rows=rows, first_s=times[0], steady_s=min(times[1:]),
                    songs_per_s=rows / min(times[1:]),
                    memory_stats=devices[0].memory_stats())

    return 0


if __name__ == "__main__":
    sys.exit(main())
