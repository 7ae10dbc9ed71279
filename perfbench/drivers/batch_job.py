"""Driver ``batch_job``: back-to-back whole jobs over the corpus CSV.

A traffic file for this driver lists the program's jobs that make up one
benchmark job (``"jobs": ["sentiment"]`` or ``["analyze", "sentiment"]``),
each run through the entry point the CLI calls.  Jobs are started while
``elapsed + median job time so far < seconds`` and at least once; only
whole jobs count.  Every job writes into a directory of its own.

Measures: ``items_per_s`` (songs in whole jobs over the wall time from the
first job's start to the last job's end) and ``setup_s``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List

import numpy as np

import common
import corpus
import oracle
import trace_reduce


# ------------------------------------------------------------------ set-up

def setup(cell: Dict[str, Any]) -> Dict[str, Any]:
    config, traffic = cell["config"], cell["traffic"]
    out_dir = common.fresh_dir(os.path.join(cell["out_dir"], "run"))
    spans = common.HostSpans()

    devices = common.require_devices(cell["chips"], cell["rehearsal"])
    from music_analyst_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )

    cache_dir = enable_persistent_compilation_cache()
    compiles = common.CompileLog()

    with spans.span("perfbench:corpus"):
        csv_path = corpus.ensure_corpus(
            common.OUT_ROOT, config["corpus"]["generator"], cell["seed"])
    songs = int(config["corpus"]["generator"]["songs"])

    state: Dict[str, Any] = {
        "cell": cell, "config": config, "traffic": traffic,
        "out_dir": out_dir, "spans": spans, "devices": devices,
        "compiles": compiles, "csv_path": csv_path, "songs": songs,
        "cache_dir": cache_dir, "checks": {}, "setup": {},
    }
    mesh_shape = config.get("mesh")
    rows_per_chip = int(config["fixed"]["rows_per_chip"])
    state["batch_size"] = rows_per_chip * (
        int(np.prod(list(mesh_shape.values()))) if mesh_shape else 1)

    if "sentiment" in traffic["jobs"]:
        _setup_sentiment(state, mesh_shape)
    if "analyze" in traffic["jobs"]:
        _setup_analyze(state)

    done = time.monotonic()
    setup_events = compiles.between(cell["t_process"], done)
    state["setup"].update(
        compile_s=sum(d for _, d in setup_events),
        compiles=len(setup_events),
        setup_s=done - cell["t_process"],
    )
    common.note(setup=state["setup"], checks=state["checks"],
                cache_dir=cache_dir, corpus=csv_path)
    return state


def _setup_sentiment(state, mesh_shape) -> None:
    """Build the backend once, as ``sentiment --model <name> [--devices N]``
    does, hold a sample of the corpus's first batch against the reference,
    and run one whole job outside the window: it compiles the full and the
    last, partial batch shape and pays what the program pays once a process
    (its first manifest, its lazy imports), so the window holds steady jobs
    and those costs show in ``setup_s``."""
    from music_analyst_tpu.engines.sentiment import get_backend

    config, spans = state["config"], state["spans"]
    model = config["model"]
    mesh = None
    if mesh_shape:
        from music_analyst_tpu.parallel.mesh import data_parallel_mesh

        mesh = data_parallel_mesh(int(mesh_shape["dp"]))
    t0 = time.monotonic()
    backend = get_backend(model["name"], mesh=mesh)
    state["setup"]["backend_init_s"] = time.monotonic() - t0
    spans.add("perfbench:backend_init", t0, state["setup"]["backend_init_s"])
    for key in ("dim", "n_layers", "n_heads", "hidden_dim", "vocab_size"):
        if getattr(backend.config, key) != config[key]:
            raise SystemExit(
                f"perfbench: the backend's {key} is "
                f"{getattr(backend.config, key)}, the configuration file "
                f"says {config[key]}")
    if backend.max_len != model["max_len"] or backend.config.dtype != model["dtype"]:
        raise SystemExit("perfbench: max_len or dtype differ from the file")
    state["backend"] = backend

    first = [row[3] for row in corpus.read_rows(
        state["csv_path"], limit=state["batch_size"])]
    with spans.span("perfbench:first_batch"):
        handle = backend.launch(backend.transfer(backend.prepare(first)))
        _, parts = handle
        (_, classes, confidence, _), = parts
        classes = np.asarray(classes)[:len(first)]
        confidence = np.asarray(confidence)[:len(first)].astype(np.float64)
        labels = backend.collect(handle)
    p_system = np.where(classes == 1, confidence, 1.0 - confidence)

    with spans.span("perfbench:reference"):
        from reference import distilbert_f32

        rng = np.random.default_rng([state["cell"]["seed"], 64])
        sample = np.sort(rng.choice(
            len(first), size=min(model["reference_sample"], len(first)),
            replace=False))
        ids, lengths = backend.tokenizer.encode_batch(
            [first[i] for i in sample], backend.max_len)
        p_ref = distilbert_f32.positive_probability(
            backend.params, ids, lengths,
            config["n_layers"], config["n_heads"])
    tol = distilbert_f32.TOLERANCE
    diff = np.abs(p_system[sample] - p_ref)
    compared, wrong = 0, []
    for i, p in zip(sample, p_ref):
        want = common.expected_label(float(p), backend.neutral_threshold, tol)
        if want is None or not first[i].strip():
            continue
        compared += 1
        if labels[i] != want:
            wrong.append(int(i))
    with spans.span("perfbench:warmup_job"):
        state["first_counts"] = _run_sentiment(
            state, os.path.join(state["out_dir"], "warmup", "sentiment"))["counts"]
    state["checks"]["reference"] = {
        "rows": int(len(sample)), "tolerance": tol,
        "max_abs_diff": float(diff.max()), "median_abs_diff": float(np.median(diff)),
        "labels_compared": compared, "labels_wrong": wrong,
        "ok": bool(diff.max() <= tol and not wrong),
    }


def _setup_analyze(state) -> None:
    """The oracle's two tables (made once per corpus), and one whole job
    outside the window: its programs depend on the corpus, so nothing but
    the job itself warms them."""
    directory = os.path.dirname(state["csv_path"])
    paths = {name: os.path.join(directory, "oracle_" + name)
             for name in ("word_counts.csv", "top_artists.csv")}
    with state["spans"].span("perfbench:oracle"):
        if not all(os.path.exists(p) for p in paths.values()):
            for name, data in oracle.expected_tables(state["csv_path"]).items():
                with open(paths[name] + ".tmp", "wb") as fh:
                    fh.write(data)
                os.replace(paths[name] + ".tmp", paths[name])
    state["oracle_paths"] = paths
    with state["spans"].span("perfbench:warmup_job"):
        part = _run_analyze(
            state, os.path.join(state["out_dir"], "warmup", "analyze"))
    state["checks"]["analyze_warmup"] = _tables_equal(state, part["dir"])


# -------------------------------------------------------------------- jobs

def _run_sentiment(state, directory: str) -> Dict[str, Any]:
    from music_analyst_tpu.engines.sentiment import run_sentiment

    result = run_sentiment(
        state["csv_path"], backend=state["backend"],
        batch_size=state["batch_size"], output_dir=directory, quiet=True,
    )
    return {"dir": directory, "counts": dict(result.counts),
            "songs": sum(result.counts.values())}


def _run_analyze(state, directory: str) -> Dict[str, Any]:
    from music_analyst_tpu.engines.wordcount import run_analysis

    mesh = None
    if state["config"].get("mesh"):
        from music_analyst_tpu.parallel.mesh import data_parallel_mesh

        mesh = data_parallel_mesh(int(state["config"]["mesh"]["dp"]))
    result = run_analysis(
        state["csv_path"], output_dir=directory, mesh=mesh, quiet=True,
        ingest_backend=state["config"]["fixed"]["ingest"],
        use_corpus_cache=False,
    )
    return {"dir": directory, "songs": result.total_songs,
            "timings": dict(result.timings)}


_JOBS = {"sentiment": _run_sentiment, "analyze": _run_analyze}


def _tables_equal(state, directory: str) -> Dict[str, Any]:
    out = {}
    for name, want_path in state["oracle_paths"].items():
        with open(os.path.join(directory, name), "rb") as fh:
            got = fh.read()
        with open(want_path, "rb") as fh:
            out[name] = got == fh.read()
    out["ok"] = all(out.values())
    return out


def _one_job(state, index: int) -> Dict[str, Any]:
    spans = state["spans"]
    job: Dict[str, Any] = {"index": index, "parts": {}}
    t0 = time.monotonic()
    for kind in state["traffic"]["jobs"]:
        directory = os.path.join(state["out_dir"], f"job{index}", kind)
        p0 = time.monotonic()
        part = _JOBS[kind](state, directory)
        part["seconds"] = time.monotonic() - p0
        spans.add(f"perfbench:job.{kind}", p0, part["seconds"])
        job["parts"][kind] = part
    job["t0"], job["seconds"] = t0, time.monotonic() - t0
    spans.add("perfbench:job", t0, job["seconds"])
    return job


# --------------------------------------------------------------------- run

def run(state: Dict[str, Any], seconds: float, trace: bool) -> Dict[str, Any]:
    cell, songs = state["cell"], state["songs"]
    jobs: List[Dict[str, Any]] = []
    xplane = None
    if trace:
        # one whole job, traced, before the window: tracing is not in a rate
        tracer = common.DeviceTrace(os.path.join(state["out_dir"], "trace"))
        tracer.start()
        with tracer.region():
            jobs.append(_one_job(state, 0))
        xplane = tracer.stop()
    t_window = time.monotonic()
    timed: List[Dict[str, Any]] = []
    while True:
        timed.append(_one_job(state, len(jobs)))
        jobs.append(timed[-1])
        elapsed = time.monotonic() - t_window
        if elapsed + common.median([j["seconds"] for j in timed]) >= seconds:
            break
    wall = timed[-1]["t0"] + timed[-1]["seconds"] - timed[0]["t0"]
    window_compiles = state["compiles"].between(
        cell["t_process"] + state["setup"]["setup_s"], time.monotonic())

    # --- correct -------------------------------------------------------
    checks = dict(state["checks"])
    failed = 0
    for job in jobs:
        ok = all(p["songs"] == songs for p in job["parts"].values())
        if "sentiment" in job["parts"]:
            ok = ok and (job["parts"]["sentiment"]["counts"]
                         == state["first_counts"])
        if "analyze" in job["parts"]:
            ok = ok and _tables_equal(state, job["parts"]["analyze"]["dir"])["ok"]
        job["ok"] = ok
        failed += not ok
    checks["jobs_ok"] = failed == 0
    checks["window_compiles"] = len(window_compiles)
    correct = (failed == 0 and not window_compiles
               and all(c.get("ok", True) for c in checks.values()
                       if isinstance(c, dict)))

    for job in jobs:
        for part in job["parts"].values():
            manifest = os.path.join(part["dir"], "run_manifest.json")
            part["manifest"] = (common.load_json(manifest)
                                if os.path.exists(manifest) else None)
            metrics = os.path.join(part["dir"], "performance_metrics.json")
            if os.path.exists(metrics):
                part["performance_metrics"] = common.load_json(metrics)

    device = common.device_report(state["devices"])
    artifacts = {
        "config": state["config"], "traffic": state["traffic"],
        "chips": cell["chips"], "device": device, "setup": state["setup"],
        "window_compiles": len(window_compiles), "jobs": jobs,
        "songs": songs, "batch_size": state["batch_size"], "trace": None,
    }
    breakdown = None
    if xplane is not None:
        host_spans = list(state["spans"].spans)
        for part in jobs[0]["parts"].values():
            host_spans += common.telemetry_spans(
                os.path.join(part["dir"], "telemetry.jsonl"),
                ignore=state["traffic"].get("ignore_spans", ()))
        reduced = trace_reduce.reduce_file(
            xplane, host_spans, rehearsal=cell["rehearsal"])
        artifacts["trace"] = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {
            "device_ops": [list(kv) for kv in reduced["device_ops"][:10]],
            "idle_gaps": [list(kv) for kv in reduced["idle_gaps"][:10]],
        }
        with open(os.path.join(cell["out_dir"], "trace_reduced.json"), "w",
                  encoding="utf-8") as fh:
            json.dump(reduced, fh)
        common.note(trace=xplane, idle_share_mean=reduced["idle_share_mean"],
                    idle_share_worst=reduced["idle_share_worst"],
                    longest_gap_s={n: d["longest_gap_s"]
                                   for n, d in reduced["devices"].items()})

    common.note(
        jobs=[{"seconds": j["seconds"], "ok": j["ok"],
               "parts": {k: p["seconds"] for k, p in j["parts"].items()}}
              for j in jobs],
        checks=checks, wall_s=wall)
    return {
        "correct": correct, "attempted": len(jobs), "failed": failed,
        "device": device, "breakdown": breakdown, "artifacts": artifacts,
        "measures": {
            "items_per_s": songs * len(timed) / wall,
            "setup_s": state["setup"]["setup_s"],
        },
    }
