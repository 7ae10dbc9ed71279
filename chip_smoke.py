#!/usr/bin/env python3
"""Quickest proof that the system still starts, and is right, on the chip.

    python3 chip_smoke.py              # on a machine with a TPU
    python3 chip_smoke.py --rehearsal  # sandbox: tiny sizes, CPU, interpret

Drives the main path once through the entry points a user would call
(``python -m music_analyst_tpu analyze | sentiment | serve``), at the full
width of the models the repo ships (DistilBERT as shipped; Pallas kernels
at Llama-3-8B head geometry and at the ``llama3-tiny`` geometry ``serve``
really builds), with random weights made from seeds, and checks what
comes out by the repo's own means: artifacts byte-equal to the jax-free
Python oracle, kernels against their f32 references under the tolerance
written below, deterministic replies, and every leg's ``run_manifest.json``
naming the device it ran on.

Rules this file keeps:

* **One process per chip.**  This parent imports neither ``jax`` nor any
  module that initialises a backend.  Each leg is one child at a time,
  finished (or killed) before the next starts.
* **No accelerator, no result.**  Without ``--rehearsal`` a leg whose
  manifest does not say ``platform == "tpu"`` fails the run: non-zero
  exit, nothing on stdout.  Progress goes to stderr.
* **Caches.**  The XLA compile cache is the only cache in play and
  follows the repo's rule (``$JAX_COMPILATION_CACHE_DIR`` if set, else
  the fixed ``<checkout>/.jax_cache``); corpus, weight and response
  caches are off.  The corpus is generated per run from a seed; the
  native library is rebuilt on this machine from ``native/ingest.cpp``.
* **Observations, not metrics.**  Songs, wall and compile seconds are
  printed as observations of one run.  Nothing here is a benchmark.

On success the last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import csv
import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Everything must fit the driver's 1200 s, compilation included; no one
# child gets more than LEG_CAP_S of it.
DEADLINE_S = 1150.0
LEG_CAP_S = 600.0

# Kernel-vs-f32-reference tolerance, elementwise:
#     |kernel - reference| <= KERNEL_ATOL + KERNEL_RTOL * |reference|
# Kernel outputs are bf16 (8-bit significand: rounding alone is up to
# 0.4% of the value) and the streaming paged body rounds probabilities
# to bf16 before the PV matmul, so honest error is a couple of bf16
# half-ulps.  Inputs are drawn so outputs are O(1); a wrong mask column,
# a dropped scale or an fp8-class precision loss moves outputs by
# O(0.1-1) and fails.
KERNEL_ATOL = 1e-2
KERNEL_RTOL = 1e-2

class LegFailed(Exception):
    pass


def log(message: str) -> None:
    print(f"[chip_smoke] {message}", file=sys.stderr, flush=True)


def require(condition: bool, message: str) -> None:
    if not condition:
        raise LegFailed(message)


# ------------------------------------------------------------------ children


class Smoke:
    """Shared state of one smoke run: sizes, output dir, the deadline,
    and what the first leg's manifest said the device is."""

    def __init__(self, out: str, rehearsal: bool) -> None:
        self.out = out
        self.rehearsal = rehearsal
        self.started = time.monotonic()
        self.device = None        # {"platform", "kinds", "count"} of leg 1
        self.jax_version = None
        self.observations = {}
        self.legs = []
        env = dict(os.environ)
        # The only cache in play is the XLA compile cache.
        env["MUSICAAL_CORPUS_CACHE"] = "off"
        env["MUSICAAL_WQ_CACHE"] = "off"
        env["MUSICAAL_RESPONSE_CACHE"] = "off"
        for name in ("MUSICAAL_FAULTS", "MUSICAAL_SERVE_REPLICAS",
                     "MUSICAAL_SERVE_TP", "MUSICAAL_SERVE_JOURNAL"):
            env.pop(name, None)
        if rehearsal:
            env["JAX_PLATFORMS"] = "cpu"
        self.env = env

    @property
    def platform(self) -> str:
        return "cpu" if self.rehearsal else "tpu"

    def remaining(self) -> float:
        return DEADLINE_S - (time.monotonic() - self.started)

    def path(self, *parts: str) -> str:
        return os.path.join(self.out, *parts)

    def run(self, leg: str, argv, *, stdin_text: str = "") -> str:
        """Run one child to its end — the only process alive besides this
        one — and return its stdout.  stderr goes to a file under the
        leg's directory; a non-zero exit or a timeout fails the leg."""
        leg_dir = self.path(leg)
        os.makedirs(leg_dir, exist_ok=True)
        budget = min(LEG_CAP_S, self.remaining())
        require(budget > 5.0, f"{leg}: no time left inside {DEADLINE_S:.0f}s")
        stderr_path = os.path.join(leg_dir, "stderr.log")
        with open(stderr_path, "ab") as stderr:
            proc = subprocess.Popen(
                argv, cwd=ROOT, env=self.env, stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, stderr=stderr,
                start_new_session=True,
            )
            try:
                stdout, _ = proc.communicate(
                    stdin_text.encode("utf-8"), timeout=budget
                )
            except subprocess.TimeoutExpired:
                _stop(proc)
                raise LegFailed(
                    f"{leg}: child still running after {budget:.0f}s "
                    f"(killed); stderr in {stderr_path}"
                )
            except BaseException:
                _stop(proc)
                raise
        if proc.returncode != 0:
            raise LegFailed(
                f"{leg}: child exited {proc.returncode}; stderr "
                f"({stderr_path}) ends:\n{_tail(stderr_path)}"
            )
        return stdout.decode("utf-8", "replace")

    def cli(self, leg: str, *args: str, stdin_text: str = "") -> str:
        return self.run(
            leg, [sys.executable, "-m", "music_analyst_tpu", *args],
            stdin_text=stdin_text,
        )

    def serve(self, leg: str, requests, *args: str):
        """One ``serve --stdio`` process fed ``requests`` then EOF: every
        request answered ok, in arrival order.  Returns the raw reply
        lines and their parsed form."""
        stdout = self.cli(
            leg, "serve", "--stdio", "--no-response-cache", "--quiet", *args,
            "--telemetry-dir", self.path(leg, "telemetry"),
            stdin_text="".join(json.dumps(r) + "\n" for r in requests),
        )
        lines = [line for line in stdout.splitlines() if line.strip()]
        replies = [json.loads(line) for line in lines]
        require([r.get("id") for r in replies] == [r["id"] for r in requests],
                f"{leg}: replies missing or out of order")
        require(all(r.get("ok") for r in replies),
                f"{leg}: {[r for r in replies if not r.get('ok')][:2]}")
        return lines, replies

    # ------------------------------------------------------- manifest checks

    def manifest(self, leg: str) -> dict:
        """Load a leg's run manifest and hold it to the common contract:
        the device is the one this run is about, and nothing on the way
        gave way quietly."""
        path = self.path(leg, "telemetry", "run_manifest.json")
        require(os.path.exists(path), f"{leg}: no run_manifest.json")
        with open(path, encoding="utf-8") as fh:
            manifest = json.load(fh)
        device = manifest.get("device") or {}
        self.same_device(
            leg, device.get("platform"), device.get("kinds"),
            device.get("count"), manifest.get("jax_version"),
        )
        require("degraded" not in manifest, f"{leg}: manifest says degraded")
        counters = manifest.get("counters") or {}
        bad = {
            name: n for name, n in counters.items()
            if n and (name.startswith("failover.") or name in (
                "serving.residency_reloads", "serving.request_failed",
                "serving.isolation_retries",
            ))
        }
        require(not bad, f"{leg}: fallback counters fired: {bad}")
        requests = (manifest.get("serving") or {}).get("requests") or {}
        for key in ("failed", "isolation_retries", "failover_reloads"):
            require(not requests.get(key),
                    f"{leg}: serving.requests.{key} = {requests.get(key)}")
        return manifest

    def same_device(self, leg, platform, kinds, count, jax_version) -> None:
        """Every leg ran on the accelerator, and on the same one."""
        require(
            platform == self.platform,
            f"{leg}: ran on platform {platform!r}, not {self.platform!r} "
            "(JAX found no accelerator?)",
        )
        seen = {"platform": platform, "kinds": kinds, "count": count}
        if self.device is None:
            self.device, self.jax_version = seen, jax_version
            log(f"device: {seen}, jax {jax_version}")
        require(seen == self.device and jax_version == self.jax_version,
                f"{leg}: {seen} / jax {jax_version} differs from "
                f"{self.device} / jax {self.jax_version}")

    def compile_cache(self, manifest: dict) -> dict:
        events = manifest.get("jax_events") or {}

        def count(name):
            return (events.get(f"/jax/compilation_cache/{name}") or {}).get(
                "count", 0)

        return {
            "compile_seconds": (manifest.get("compile") or {}).get("seconds"),
            "cache_hits": count("cache_hits"),
            "cache_misses": count("cache_misses"),
        }


def _stop(proc: subprocess.Popen) -> None:
    """SIGTERM (a server drains and reaps its own workers), then SIGKILL
    the child's whole session."""
    for sig, wait_s in ((signal.SIGTERM, 20.0), (signal.SIGKILL, 10.0)):
        try:
            os.killpg(proc.pid, sig)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            proc.wait(timeout=wait_s)
            return
        except subprocess.TimeoutExpired:
            continue


def _tail(path: str, limit: int = 1500) -> str:
    try:
        with open(path, "rb") as fh:
            fh.seek(0, os.SEEK_END)
            fh.seek(max(0, fh.tell() - limit))
            return fh.read().decode("utf-8", "replace")
    except OSError:
        return ""


def _read_bytes(path: str) -> bytes:
    with open(path, "rb") as fh:
        return fh.read()


def _labels(details_csv: str):
    with open(details_csv, newline="", encoding="utf-8") as fh:
        return [row["label"] for row in csv.DictReader(fh)]


# ---------------------------------------------------------------------- legs


def leg_analyze(smoke: Smoke, corpus: str) -> None:
    """Corpus word/artist count through the streaming device path, native
    ingest built here, artifacts equal to the jax-free Python oracle."""
    args = ["analyze", corpus, "--ingest", "native", "--no-corpus-cache",
            "--output-dir", smoke.path("analyze"),
            "--telemetry-dir", smoke.path("analyze", "telemetry")]
    if smoke.rehearsal:
        args += ["--chunk-songs", "64"]  # too small to stream by itself
    smoke.cli("analyze", *args)
    manifest = smoke.manifest("analyze")
    context = manifest.get("context") or {}
    require((context.get("chunk_songs") or 0) > 0,
            f"analyze: not the streaming device path: {context}")
    if not smoke.rehearsal:
        require(manifest["counters"].get("histogram.stream_chunks", 0) > 1,
                "analyze: corpus did not stream in chunks")
    _compare_to_oracle(smoke, corpus, "analyze")
    smoke.observations["analyze"] = {
        "mesh_shape": context.get("mesh_shape"),
        "chunk_songs": context.get("chunk_songs"),
        "wall_seconds": manifest.get("wall_seconds"),
    }


def _oracle(smoke: Smoke, corpus: str) -> str:
    """``word_counts.csv`` / ``top_artists.csv`` from the pure-Python
    ingest + numpy counts — no jax anywhere near it.  Written once."""
    out = smoke.path("oracle")
    if os.path.isdir(out):
        return out
    import numpy as np

    from music_analyst_tpu.data.csv_io import (
        sort_count_entries,
        write_count_csv,
    )
    from music_analyst_tpu.data.ingest import ingest_python

    os.makedirs(out)
    result = ingest_python(_read_bytes(corpus))
    for name, label, ids, vocab in (
        ("word_counts.csv", "word", result.word_ids, result.word_vocab),
        ("top_artists.csv", "artist", result.artist_ids, result.artist_vocab),
    ):
        counts = np.bincount(ids[ids >= 0], minlength=max(1, len(vocab)))
        write_count_csv(
            os.path.join(out, name), label,
            sort_count_entries(vocab.counts_to_entries(counts)), 0,
        )
    smoke.observations["corpus"] = {
        "songs": int(result.song_count),
        "tokens": int(result.token_count),
    }
    return out


def _compare_to_oracle(smoke: Smoke, corpus: str, leg: str) -> None:
    oracle = _oracle(smoke, corpus)
    for name in ("word_counts.csv", "top_artists.csv"):
        require(
            _read_bytes(smoke.path(leg, name))
            == _read_bytes(os.path.join(oracle, name)),
            f"{leg}: {name} differs from the Python oracle",
        )


def leg_sentiment(smoke: Smoke, corpus: str, songs: int, batch: int,
                  model: str) -> None:
    """``sentiment --model distilbert`` as shipped, twice, as two
    processes: the second finds the first's programs in the compile
    cache."""
    runs = []
    for leg in ("sentiment_1", "sentiment_2"):
        smoke.cli(
            leg, "sentiment", corpus, "--model", model,
            "--limit", str(songs), "--batch-size", str(batch),
            "--output-dir", smoke.path(leg),
            "--telemetry-dir", smoke.path(leg, "telemetry"),
        )
        manifest = smoke.manifest(leg)
        labels = _labels(smoke.path(leg, "sentiment_details.csv"))
        require(len(labels) == songs, f"{leg}: {len(labels)} rows")
        require(set(labels) <= {"Positive", "Neutral", "Negative"},
                f"{leg}: labels {sorted(set(labels))}")
        with open(smoke.path(leg, "sentiment_totals.json"),
                  encoding="utf-8") as fh:
            totals = json.load(fh)
        require(sum(totals.values()) == songs, f"{leg}: totals {totals}")
        runs.append({
            "songs": songs,
            "wall_seconds": manifest.get("wall_seconds"),
            **smoke.compile_cache(manifest),
            "labels": labels,
        })
    first, second = runs
    require(first.pop("labels") == second.pop("labels"),
            "sentiment: the two runs disagree on labels")
    require(second["cache_hits"] > 0,
            f"sentiment: second process had no compile-cache hits: {second}")
    # A first run that already found everything cached (the machine came
    # with a warm $JAX_COMPILATION_CACHE_DIR) has nothing to beat.  Programs
    # that compile in under the cache's 0.2 s floor (the one-row kernel of
    # the parameter init) are never kept and miss in every process, so
    # "everything cached" reads as no more misses than the second run has.
    if first["cache_misses"] > second["cache_misses"]:
        require(second["compile_seconds"] < first["compile_seconds"],
                f"sentiment: warm compile not faster: {first} -> {second}")
    smoke.observations["sentiment"] = {"cold": first, "warm": second}


def leg_serve_encoder(smoke: Smoke, texts, model: str) -> None:
    """Resident encoder server: distinct sentiment requests, a wordcount,
    stats, EOF — every reply ok, batches dispatched, clean exit."""
    requests = [
        {"id": f"s{i}", "op": "sentiment", "text": text}
        for i, text in enumerate(texts)
    ]
    requests.append(
        {"id": "w", "op": "wordcount", "text": "hello hello world"})
    requests.append({"id": "stats", "op": "stats"})
    _, replies = smoke.serve("serve_encoder", requests, "--model", model)
    by_id = {r["id"]: r for r in replies}
    require(by_id["w"]["counts"] == {"hello": 2, "world": 1},
            f"serve_encoder: wordcount {by_id['w']}")
    manifest = smoke.manifest("serve_encoder")
    served = manifest["serving"]["requests"]
    require(served["batches"] > 0 and served["completed"] == len(texts) + 1,
            f"serve_encoder: {served}")
    require(not served.get("cache_hits"), "serve_encoder: cache answered")
    smoke.observations["serve_encoder"] = {
        "requests": len(requests), "batches": served["batches"],
        "warmup": (manifest["serving"].get("residency") or {}).get("warmup"),
    }


def leg_kernels(smoke: Smoke) -> None:
    """The Pallas kernels, compiled by Mosaic, against their f32
    references — in a child that imports jax (this process must not)."""
    argv = [sys.executable, os.path.abspath(__file__),
            "--kernels-child", smoke.path("kernels")]
    if smoke.rehearsal:
        argv.append("--rehearsal")
    smoke.run("kernels", argv)
    with open(smoke.path("kernels", "kernels.json"), encoding="utf-8") as fh:
        report = json.load(fh)
    device = report["device"]
    smoke.same_device("kernels", device["platform"], [device["kind"]],
                      device["count"], report["jax_version"])
    for case in report["cases"]:
        require(case["ok"], f"kernels: {case}")
    smoke.observations["kernels"] = report["cases"]


def leg_serve_decoder(smoke: Smoke, texts) -> None:
    """``serve --model llama3-tiny`` — the decoder the CLI builds without
    a checkpoint — with default paging: ``generate`` through the paged
    runtime and the Mosaic-compiled kernel, ``sentiment`` through label
    scoring; float and int8 KV; same bytes on a second run; no program
    compiled after warm-up."""
    requests = [
        {"id": f"g{i}", "op": "generate", "text": text, "max_new_tokens": 16}
        for i, text in enumerate(texts[:8])
    ] + [
        {"id": f"s{i}", "op": "sentiment", "text": text}
        for i, text in enumerate(texts[8:12])
    ]
    observed = {}
    for kv_quant in ("none", "int8"):
        runs = []
        for attempt in (1, 2):
            leg = f"serve_decoder_{kv_quant}_{attempt}"
            lines, replies = smoke.serve(
                leg, requests, "--model", "llama3-tiny",
                "--kv-quant", kv_quant,
            )
            for reply in replies:
                if reply["op"] == "generate":
                    require(reply.get("tokens", 0) >= 1
                            and isinstance(reply.get("text"), str),
                            f"{leg}: empty generation {reply}")
                else:
                    require(reply.get("label") in
                            ("Positive", "Neutral", "Negative"),
                            f"{leg}: {reply}")
            manifest = smoke.manifest(leg)
            decode = manifest["serving"]["decode"]
            require(decode["kv_backend"] == "paged"
                    and decode["kv_quant"]["scheme"] == kv_quant
                    and not decode["kv_quant"].get("degraded"),
                    f"{leg}: decode backend {decode['kv_backend']}, "
                    f"kv_quant {decode['kv_quant']}")
            require(decode["completed"] == 8 and not decode["failed"],
                    f"{leg}: decode completed {decode['completed']}, "
                    f"failed {decode['failed']}")
            require(
                decode["compiled_variants"] == decode["warmup"]["programs"],
                f"{leg}: programs compiled after warm-up: "
                f"{decode['compiled_variants']} vs {decode['warmup']}")
            runs.append(lines)
        require(runs[0] == runs[1],
                f"serve_decoder {kv_quant}: second run's bytes differ")
        observed[kv_quant] = {
            "tokens_generated": decode["tokens_generated"],
            "decode_dispatches": decode["decode_dispatches"],
            "warmup": decode["warmup"],
        }
    smoke.observations["serve_decoder"] = observed


def leg_trace(smoke: Smoke, corpus: str, model: str, songs: int,
              batch: int) -> None:
    """``--profile-dir`` leaves a device trace on disk (S1 reads it next)."""
    profile = smoke.path("trace", "profile")
    smoke.cli(
        "trace", "sentiment", corpus, "--model", model,
        "--limit", str(songs), "--batch-size", str(batch),
        "--output-dir", smoke.path("trace"),
        "--telemetry-dir", smoke.path("trace", "telemetry"),
        "--profile-dir", profile,
    )
    smoke.manifest("trace")
    traces = [
        p for p in glob.glob(
            os.path.join(profile, "**", "*.xplane.pb"), recursive=True)
        if os.path.getsize(p) > 0
    ]
    require(traces, f"trace: no non-empty *.xplane.pb under {profile}")
    require(os.path.exists(os.path.join(profile, "trace_spans.json")),
            "trace: no trace_spans.json")
    require(os.path.exists(os.path.join(profile, "op_scopes.json")),
            "trace: no op_scopes.json")
    smoke.observations["trace"] = {
        "xplane_bytes": sum(os.path.getsize(p) for p in traces)}


def leg_four_chips(smoke: Smoke, corpus: str, songs: int, batch: int,
                   texts) -> None:
    """Only on a host that shows >= 4 TPU devices — never emulated."""
    for devices in ("1", "4"):
        leg = f"four_analyze_{devices}"
        smoke.cli(
            leg, "analyze", corpus, "--ingest", "native",
            "--no-corpus-cache", "--no-split", "--devices", devices,
            "--output-dir", smoke.path(leg),
            "--telemetry-dir", smoke.path(leg, "telemetry"),
        )
        shape = smoke.manifest(leg)["context"].get("mesh_shape")
        require(shape == {"dp": int(devices)}, f"{leg}: mesh {shape}")
        _compare_to_oracle(smoke, corpus, leg)
    # dp=4 against one chip at the same rows per chip (batch 4,096 over
    # four chips = 1,024 each): every label equal.  The comparison is not
    # made at equal --batch-size because XLA compiles a different program
    # per batch shape and one chip alone then disagrees with itself: on
    # the v5e, batch 4,096 vs 1,024 on ONE chip flips 107 of these 16,384
    # random-init labels (confidences move <= 0.006, every flipped row
    # within 0.002 of a label boundary), while dp=4 is bit-equal to one
    # chip at the matched shape both ways (control run, PERF.md).
    runs = {}
    for leg, devices, rows in (("four_sentiment", ["--devices", "4"], batch),
                               ("four_sentiment_one_chip", [], batch // 4)):
        smoke.cli(
            leg, "sentiment", corpus, "--model", "distilbert", *devices,
            "--limit", str(songs), "--batch-size", str(rows),
            "--output-dir", smoke.path(leg),
            "--telemetry-dir", smoke.path(leg, "telemetry"),
        )
        runs[leg] = (smoke.manifest(leg),
                     _labels(smoke.path(leg, "sentiment_details.csv")))
    (manifest, four), (one_manifest, one) = (
        runs["four_sentiment"], runs["four_sentiment_one_chip"])
    differing = sum(a != b for a, b in zip(four, one))
    require(len(four) == len(one) == songs and differing == 0,
            f"four_sentiment: {differing} of {len(one)} labels differ from "
            f"one chip at the same {batch // 4} rows per chip")
    peaks, one_peaks = (
        [(stats or {}).get("peak_bytes_in_use", 0)
         for stats in m["device"]["memory_stats"][:4]]
        for m in (manifest, one_manifest)
    )
    # Weights replicate over dp and every chip takes a batch shard: all
    # four hold the model, not just device 0.  Without --devices only
    # device 0 does.
    require(len(peaks) == 4 and min(peaks) >= 64 << 20
            and min(peaks) >= max(peaks) / 4,
            f"four_sentiment: per-device peak bytes {peaks}")
    require(one_peaks[0] >= 64 << 20 and max(one_peaks[1:]) < 1 << 20,
            f"four_sentiment_one_chip: per-device peak bytes {one_peaks}")
    # One process per chip: four pinned one-chip workers behind the
    # router, whose parent holds no backend.
    leg = "four_replicas"
    requests = [
        {"id": f"s{i}", "op": "sentiment", "text": text}
        for i, text in enumerate(texts)
    ]
    smoke.serve(leg, requests, "--model", "distilbert", "--replicas", "4")
    manifest = smoke.manifest(leg)
    router = manifest["serving"]["router"]
    require("replica stats" in manifest["device"].get("source", ""),
            f"{leg}: router parent described a device of its own: "
            f"{manifest['device']}")
    require(router["replica_count"] == 4
            and not router["health_transitions"]
            and all(r["dispatched"] > 0
                    for r in router["replicas"].values()),
            f"{leg}: {router}")
    logs = glob.glob(smoke.path(leg, "telemetry", "replica-*.stderr.log"))
    require(len(logs) == 4, f"{leg}: worker stderr files {logs}")
    observed = {
        "labels_differing_from_one_chip_same_rows_per_chip": differing,
        "peak_bytes_in_use": peaks,
        "peak_bytes_in_use_without_devices_flag": one_peaks,
        "replica_dispatch": {
            name: r["dispatched"] for name, r in router["replicas"].items()},
    }
    # What batch shape alone does on one chip, when the sentiment leg ran.
    whole_batch = smoke.path("sentiment_1", "sentiment_details.csv")
    if os.path.exists(whole_batch):
        observed["labels_differing_one_chip_batch_%d_vs_%d" % (
            batch, batch // 4)] = sum(
                a != b for a, b in zip(_labels(whole_batch), one))
    smoke.observations["four_chips"] = observed


# ------------------------------------------------------------- kernels child


def kernels_child(out_dir: str, rehearsal: bool) -> int:
    """Runs in its own process: the one place this file touches jax."""
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from music_analyst_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )

    enable_persistent_compilation_cache()
    from music_analyst_tpu.models.layers import (
        causal_mask,
        dot_product_attention,
        padding_mask,
        segment_mask,
    )
    from music_analyst_tpu.ops.flash_attention import flash_attention
    from music_analyst_tpu.ops.paged_attention import (
        paged_attention,
        paged_attention_reference,
    )
    from music_analyst_tpu.ops.quant import quantize_kv_page
    from music_analyst_tpu.ops.whole_row_attention import whole_row_attention

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not rehearsal:
        print(f"kernels: JAX found no TPU ({devices[0].platform})",
              file=sys.stderr)
        return 1
    cases = []

    def run_case(name, fn, args, reference):
        lowered = jax.jit(fn).lower(*args)
        mosaic = "tpu_custom_call" in lowered.as_text()
        out = np.asarray(lowered.compile()(*args), np.float32)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(reference(), np.float32)
        require(out.shape == ref.shape, f"{name}: shape {out.shape}")
        err = np.abs(out - ref)
        used = float(np.max(err / (KERNEL_ATOL + KERNEL_RTOL * np.abs(ref))))
        cases.append({
            "kernel": name,
            "compiled_by": "mosaic" if mosaic else "interpreter",
            "max_abs_err": round(float(np.max(err)), 5),
            "max_abs_ref": round(float(np.max(np.abs(ref))), 4),
            "tolerance_used": round(used, 3),  # worst element; 1.0 = bound
            "ok": bool(
                np.isfinite(out).all() and used <= 1.0
                and (mosaic or not on_tpu)  # on a TPU: Mosaic, or fail
            ),
        })

    def paged_case(name, heads, kv_heads, head_dim, pages_per_slot, quantized):
        slots, page = 8, 16
        rng = np.random.default_rng(heads * 1000 + head_dim + quantized)
        n_pages = slots * pages_per_slot
        span = pages_per_slot * page
        total = span - 5   # off the page grid, like prompt_region + max_new
        region = total - 16
        pool = (n_pages + 1, page, kv_heads, head_dim)
        # Queries scaled up so the softmax is peaked and outputs are O(1).
        q = jnp.asarray(3.0 * rng.normal(size=(slots, 1, heads, head_dim)),
                        jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=pool), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=pool), jnp.bfloat16)
        table = jnp.asarray(
            rng.permutation(n_pages).reshape(slots, pages_per_slot),
            jnp.int32)
        # The decode runtime's mask: the prompt, a gap, the decoded rows.
        prompt = rng.integers(1, region + 1, size=slots)
        prompt[0] = region
        steps = rng.integers(0, 16, size=slots)
        pos = np.arange(total)[None, :]
        mask = jnp.asarray(
            (pos < prompt[:, None])
            | ((pos >= region) & (pos - region <= steps[:, None])))
        scales = ()
        if quantized:
            k, k_scale = quantize_kv_page(k)
            v, v_scale = quantize_kv_page(v)
            scales = (k_scale, v_scale)

        def fn(q, k, v, table, mask, *scales):
            return paged_attention(
                q, k, v, table, mask,
                key_scale=scales[0] if scales else None,
                value_scale=scales[1] if scales else None,
                interpret=not on_tpu, stream=True,
            )

        args = (q, k, v, table, mask, *scales)
        run_case(name, fn, args,
                 lambda: paged_attention_reference(*args))

    def flash_case(name, seq, segmented):
        batch, heads, kv_heads, head_dim = 2, 8, 2, 128
        rng = np.random.default_rng(seq + segmented)
        q = jnp.asarray(
            3.0 * rng.normal(size=(batch, seq, heads, head_dim)),
            jnp.bfloat16)
        k, v = (
            jnp.asarray(rng.normal(size=(batch, seq, kv_heads, head_dim)),
                        jnp.bfloat16)
            for _ in range(2)
        )
        mask = causal_mask(seq, seq, 0)
        segments = None
        if segmented:
            # Packed documents of uneven length, different per row.
            cuts = np.sort(rng.integers(1, seq, size=(batch, 7)), axis=1)
            segments = jnp.asarray(
                (np.arange(seq)[None, None, :] >= cuts[:, :, None]).sum(1),
                jnp.int32)
            mask = mask & segment_mask(segments)
        args = (q, k, v) + ((segments,) if segmented else ())

        def fn(q, k, v, segments=None):
            return flash_attention(
                q, k, v, causal=True, q_segment_ids=segments,
                interpret=not on_tpu,
            )

        def dense():
            f32 = (x.astype(jnp.float32) for x in (q, k, v))
            return dot_product_attention(*f32, mask)

        run_case(name, fn, args, dense)

    def whole_row_case(name, rows):
        seq, heads, head_dim = 128, 12, 64  # the encoder's attention
        rng = np.random.default_rng(rows)
        q = jnp.asarray(
            3.0 * rng.normal(size=(rows, seq, heads, head_dim)), jnp.bfloat16)
        k, v = (
            jnp.asarray(rng.normal(size=(rows, seq, heads, head_dim)),
                        jnp.bfloat16)
            for _ in range(2)
        )
        lengths = rng.integers(1, seq + 1, size=rows)
        lengths[0], lengths[-1] = 1, seq
        lengths = jnp.asarray(lengths, jnp.int32)

        def fn(q, k, v, lengths):
            return whole_row_attention(
                q, k, v, lengths, interpret=not on_tpu)

        def dense():
            f32 = (x.astype(jnp.float32) for x in (q, k, v))
            return dot_product_attention(*f32, padding_mask(lengths, seq))

        run_case(name, fn, (q, k, v, lengths), dense)

    # Llama-3-8B heads over a >= 1,024-token span, and the llama3-tiny
    # geometry serve really builds (65 pages: region 1024 + 16 new).
    for quantized in (False, True):
        kv = "int8" if quantized else "bf16"
        paged_case(f"paged_attention llama3-8b {kv}", 32, 8, 128, 65,
                   quantized)
        paged_case(f"paged_attention llama3-tiny {kv}", 8, 4, 16, 65,
                   quantized)
    seq = 512 if rehearsal else 4096
    flash_case(f"flash_attention S={seq} gqa causal", seq, False)
    flash_case(f"flash_attention S={seq} gqa causal segments", seq, True)
    # The sentiment job's tail batch: 38 blocks of 8 rows and one of 2.
    rows = 21 if rehearsal else 306
    whole_row_case(f"whole_row_attention {rows}x12x128x64 padded", rows)

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kernels.json"), "w",
              encoding="utf-8") as fh:
        json.dump({
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)},
            "jax_version": jax.__version__,
            "tolerance": {"atol": KERNEL_ATOL, "rtol": KERNEL_RTOL},
            "cases": cases,
        }, fh, indent=1)
    for case in cases:
        print(f"kernels: {case}", file=sys.stderr)
    return 0 if all(case["ok"] for case in cases) else 1


# ---------------------------------------------------------------------- main


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearsal", action="store_true",
        help="Sandbox rehearsal: tiny sizes on the CPU with the Pallas "
             "interpreter.  Checks the script, proves nothing about a chip.")
    parser.add_argument(
        "--out", default=os.path.join(ROOT, "chip_smoke_out"),
        help="Directory for every leg's outputs (emptied first)")
    parser.add_argument(
        "--legs", default=None, metavar="A,B",
        help="Run only these legs (debugging; the proof is the full run)")
    parser.add_argument("--kernels-child", metavar="DIR",
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.kernels_child:
        return kernels_child(args.kernels_child, args.rehearsal)

    # Repo imports up front: in a directory that holds this file and
    # nothing else of the repo, this is where the run ends.
    sys.path.insert(0, ROOT)
    from music_analyst_tpu.data.csv_io import iter_songs
    from music_analyst_tpu.data.synthetic import generate_dataset

    assert "jax" not in sys.modules, "the smoke's parent must stay off jax"

    # A terminated smoke still stops the child it started (Smoke.run).
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    out = os.path.abspath(args.out)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    smoke = Smoke(out, args.rehearsal)
    if args.rehearsal:
        songs_total, songs, batch, trace_songs, trace_batch = 300, 64, 32, 64, 32
        model, n_texts = "distilbert-tiny", 16
    else:
        # 32,768 synthetic songs are ~5.5M tokens: past the 1 << 22 where
        # analyze streams through the device on its own.  Sentiment runs
        # the r02 shape: 16,384 songs at batch 4,096, full-width model.
        songs_total, songs, batch, trace_songs, trace_batch = (
            32_768, 16_384, 4096, 2048, 1024)
        model, n_texts = "distilbert", 64
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    log(f"out={out} rehearsal={args.rehearsal} compile cache={cache_dir}")
    try:
        for step in (["make", "-C", "native", "-s", "clean"],
                     ["make", "-C", "native", "-s"]):
            subprocess.run(step, cwd=ROOT, check=True, timeout=300)
        corpus = smoke.path("corpus.csv")
        generate_dataset(corpus, num_songs=songs_total, seed=21)
        texts = []
        for _, _, text in iter_songs(corpus):
            texts.append(text)
            if len(texts) == n_texts:
                break
        require(len(set(texts)) == n_texts, "corpus texts are not distinct")

        legs = [
            ("analyze", lambda: leg_analyze(smoke, corpus)),
            ("sentiment", lambda: leg_sentiment(
                smoke, corpus, songs, batch, model)),
            ("serve_encoder", lambda: leg_serve_encoder(smoke, texts, model)),
            ("kernels", lambda: leg_kernels(smoke)),
            ("serve_decoder", lambda: leg_serve_decoder(smoke, texts)),
            ("trace", lambda: leg_trace(
                smoke, corpus, model, trace_songs, trace_batch)),
        ]
        chosen = args.legs.split(",") if args.legs else None
        known = [name for name, _ in legs] + ["four_chips"]
        require(not chosen or set(chosen) <= set(known),
                f"--legs: unknown leg in {chosen}; have {known}")
        for name, leg in legs:
            if chosen and name not in chosen:
                smoke.legs.append({"leg": name, "skipped": "--legs"})
                continue
            t0 = time.monotonic()
            leg()
            seconds = round(time.monotonic() - t0, 1)
            smoke.legs.append({"leg": name, "ok": True, "seconds": seconds})
            log(f"leg {name}: ok in {seconds}s "
                f"{json.dumps(smoke.observations.get(name))}")
        if chosen and "four_chips" not in chosen:
            smoke.legs.append({"leg": "four_chips", "skipped": "--legs"})
        elif smoke.platform == "tpu" and smoke.device["count"] >= 4:
            t0 = time.monotonic()
            leg_four_chips(smoke, corpus, songs, batch, texts)
            smoke.legs.append({"leg": "four_chips", "ok": True,
                               "seconds": round(time.monotonic() - t0, 1)})
        else:
            smoke.legs.append({
                "leg": "four_chips",
                "skipped": f"{smoke.device['count']} device(s)"
                           + (" (rehearsal)" if args.rehearsal else ""),
            })
        log(f"leg four_chips: {smoke.legs[-1]}")
        require(any(os.scandir(cache_dir)),
                f"compile cache directory {cache_dir} is empty")
    except (LegFailed, subprocess.SubprocessError, OSError) as exc:
        log(f"FAILED: {exc}")
        return 1

    summary = {
        "chip_smoke": "rehearsal" if args.rehearsal else "chip",
        "device": smoke.device,
        "jax_version": smoke.jax_version,
        "compile_cache_dir": cache_dir,
        "total_seconds": round(time.monotonic() - smoke.started, 1),
        "legs": smoke.legs,
        "observations": smoke.observations,
        "claim": None,
    }
    with open(smoke.path("summary.json"), "w", encoding="utf-8") as fh:
        json.dump(summary, fh, indent=1)
    print(json.dumps(summary))
    kinds = smoke.device["kinds"] or [None]
    final = {"ok": True, "device": {
        "platform": smoke.device["platform"], "kind": kinds[0],
        "count": smoke.device["count"]}}
    if args.rehearsal:
        final["rehearsal"] = True
    if chosen:
        final["legs"] = chosen  # a partial run says so
    print(json.dumps(final))
    return 0


if __name__ == "__main__":
    sys.exit(main())
