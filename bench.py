"""Round benchmark: batched sentiment throughput on the chip.

Headline metric (BASELINE.md): songs/sec sentiment-classified.  The driver
target is all ~1M songs in < 60 s on a v5e-8 ⇒ ≥ ~16,667 songs/s pod-wide,
i.e. ≥ ~2,083 songs/s *per chip*.  The measurement runs the full-size
DistilBERT-sst2 architecture (66M params, seq len 128, bf16) end-to-end —
host tokenization included — on however many chips are visible and reports
songs/sec with ``vs_baseline`` = measured / per-chip share of the target.

Contract: ONE process.  ``python bench.py`` runs :func:`measure` and prints
exactly one JSON line on stdout naming the ``platform`` / ``device_kind``
/ ``n_devices`` it ran on.  Any failure — including finding no TPU —
exits non-zero with the error on stderr and **no** result line: a
measurement path has no structured zero to fall back to.
``MUSICAAL_BENCH_SMOKE=1`` is the CPU-sized path the tests and ``make
smoke`` use; it labels itself ``"smoke": true`` and is never a
measurement.

Additional suites backing PERFORMANCE.md live in ``benchmarks/`` (see
``python bench.py --list-suites``).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

PER_CHIP_TARGET = 16_667 / 8  # songs/sec per chip for the <60s/1M goal
METRIC = "sentiment_songs_per_sec_distilbert"
# Wall-clock budget armed for child-spawning suites (benchmarks/_util.py).
_DEFAULT_DEADLINE_S = 480.0


def _env_deadline() -> float:
    # A malformed or non-finite/non-positive override falls back to the
    # default instead of disabling the budget.
    try:
        value = float(os.environ["MUSICAAL_BENCH_DEADLINE_S"])
    except (KeyError, ValueError):
        return _DEFAULT_DEADLINE_S
    return value if math.isfinite(value) and value > 0 else _DEFAULT_DEADLINE_S


OVERALL_DEADLINE_S = _env_deadline()


def measure() -> dict:
    """One full measurement, in this process."""
    import jax

    from music_analyst_tpu.utils.cache import (
        enable_persistent_compilation_cache,
    )

    enable_persistent_compilation_cache()
    # Memory-only telemetry (no sink — stdout is the one-line contract
    # and the cwd is not a run directory): spans + jax compile listeners
    # feed the payload's ``telemetry`` sub-object.
    from music_analyst_tpu.telemetry import (
        get_telemetry,
        install_jax_listeners,
    )

    tel = get_telemetry()
    install_jax_listeners()
    devices = jax.devices()
    n_chips = len(devices)
    platform = devices[0].platform
    smoke = os.environ.get("MUSICAAL_BENCH_SMOKE") == "1"
    if platform != "tpu" and not smoke:
        raise RuntimeError(
            f"bench.py measures on a TPU; JAX found platform {platform!r} "
            f"({n_chips} device(s)).  MUSICAAL_BENCH_SMOKE=1 runs the "
            "CPU-sized smoke shape, which is not a measurement."
        )

    from music_analyst_tpu.data.synthetic import generate_dataset
    from music_analyst_tpu.data.csv_io import iter_songs
    from music_analyst_tpu.models.distilbert import DistilBertClassifier

    # MUSICAAL_BENCH_SMOKE=1: CI-sized run (tiny model, 512 songs) so
    # `make smoke` can exercise the one-line contract and the --baseline
    # comparison in seconds.  The payload carries ``"smoke": true``.
    # The corpus is generated per run from a seed — nothing generated is
    # read back from a fixed name an earlier run may have left behind.
    with tempfile.TemporaryDirectory(prefix="musicaal_bench_") as tmp:
        dataset = os.path.join(tmp, "songs.csv")
        generate_dataset(
            dataset, num_songs=512 if smoke else 16_384, seed=11
        )
        texts = [text for _, _, text in iter_songs(dataset)]

    # Auto length bucketing: derives buckets from the first batch's token
    # lengths and only keeps ones worth a compiled shape.  On this corpus
    # (~84% of rows at the seq-128 cap) it resolves to the flat path —
    # measured either way by the `bucketing` suite.
    # MUSICAAL_BENCH_MODEL switches the headline configuration (e.g.
    # "distilbert-int8" for the dynamic-quant MXU path); the sentiment_int8
    # suite is the A/B that justifies any non-default choice.
    model = os.environ.get(
        "MUSICAAL_BENCH_MODEL", "distilbert-tiny" if smoke else "distilbert"
    )
    allowed = {
        f"distilbert{size}{quant}{pack}"
        for size in ("", "-tiny")
        for quant in ("", "-int8")
        for pack in ("", "-packed")
    }
    if model not in allowed:
        # Fail loudly: from_pretrained_or_random ignores unknown base
        # names, and a typo silently measuring the default config would
        # mislabel the headline capture.
        raise ValueError(
            f"MUSICAAL_BENCH_MODEL must be one of {sorted(allowed)}, "
            f"got {model!r}"
        )
    packed = model.endswith("-packed")
    clf = DistilBertClassifier.from_pretrained_or_random(
        model, max_len=128,
        # Packing and bucketing are exclusive right-sizing levers; the
        # bucketing suite A/Bs them against each other.
        length_buckets=None if packed else "auto",
    )
    precision = "int8" if clf.config.quant == "int8" else "bf16"
    # 8192 rows per batch: the BENCH_r02 capture's shape (the batch-size
    # choice has not been re-measured on the current code).
    batch = 256 if smoke else 8192

    # Warmup: compile + first dispatch.
    with tel.span("warmup", rows=batch):
        clf.classify_batch(texts[:batch])

    # Bounded prefetch pipeline (runtime/prefetch.py — replaces the old
    # hand-rolled one-deep loop): tokenize and transfer stages run up to
    # ``depth`` batches ahead of the device; collect() in the consumer is
    # an np.asarray readback, which waits for the device.
    from music_analyst_tpu.runtime import (
        PrefetchPipeline,
        Stage,
        resolve_prefetch_depth,
    )

    pipe = PrefetchPipeline(
        [
            Stage("tokenize", clf.prepare),
            Stage("h2d", lambda p: clf.launch(clf.transfer(p))),
        ],
        depth=resolve_prefetch_depth(),
        name="pipeline",
        sink_name="compute",
    )
    batches = (
        texts[i : i + batch] for i in range(0, len(texts), batch)
    )
    start = time.perf_counter()
    with tel.span("measure", rows=len(texts)):
        for handle in pipe.run(batches):
            clf.collect(handle)
    elapsed = time.perf_counter() - start

    songs_per_sec = len(texts) / elapsed
    tel.count("rows_classified", len(texts))
    payload = {
        "telemetry": tel.summary(top=3),
        "metric": METRIC,
        "value": round(songs_per_sec, 1),
        "unit": (
            f"songs/sec on {n_chips} {platform} chip(s), seq128 "
            f"{precision}, host tokenize included"
        ),
        "vs_baseline": round(songs_per_sec / (PER_CHIP_TARGET * n_chips), 3),
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "n_devices": n_chips,
        "length_buckets": list(clf.length_buckets or ()),
        "packed": packed,
    }
    if smoke:
        payload["smoke"] = True
    return payload



def _find_baseline(results_dir: str | None = None) -> tuple[str, float] | None:
    """Newest committed ``BENCH_r*.json`` whose parsed value is usable.

    "Usable" = the driver capture parsed to a positive headline value
    (failed rounds carry 0.0/None and cannot anchor a ratio).  Round files
    sort lexically, so the last usable one is the newest.
    """
    import glob

    if results_dir is None:
        # Round captures live next to bench.py (BENCH_r01.json, ...).
        results_dir = os.path.dirname(os.path.abspath(__file__))
    best = None
    for path in sorted(glob.glob(os.path.join(results_dir, "BENCH_r*.json"))):
        try:
            with open(path, encoding="utf-8") as fh:
                capture = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        parsed = capture.get("parsed") or {}
        value = parsed.get("value")
        if isinstance(value, (int, float)) and value > 0:
            best = (os.path.basename(path), float(value))
    return best


def _baseline_augment(threshold: float = 0.1,
                      results_dir: str | None = None):
    """``--baseline``: embed a vs-committed-capture comparison in the line.

    Returns a hook over the payload; without ``--baseline`` the payload
    prints untouched.
    """
    base = _find_baseline(results_dir)

    def augment(payload: dict) -> dict:
        if base is None:
            payload["vs_baseline_detail"] = {
                "baseline_file": None,
                "error": "no usable BENCH_r*.json capture",
            }
            return payload
        name, value = base
        current = payload.get("value") or 0.0
        payload["vs_baseline_detail"] = {
            "baseline_file": name,
            "baseline_value": value,
            "ratio": round(current / value, 3),
            "regression": bool((value - current) / value > threshold),
            "threshold": threshold,
        }
        return payload

    return augment



def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--deadline", type=float, default=None,
        help="Wall-clock budget in seconds for child-spawning suites "
             "(default $MUSICAAL_BENCH_DEADLINE_S or 480)",
    )
    parser.add_argument(
        "--suite", default=None,
        help="Run a PERFORMANCE.md suite from benchmarks/ instead of the "
             "headline metric (see --list-suites)",
    )
    parser.add_argument("--list-suites", action="store_true")
    parser.add_argument(
        "--baseline", action="store_true",
        help="Embed vs_baseline_detail (comparison against the newest "
             "usable BENCH_r*.json capture) in the output line",
    )
    parser.add_argument(
        "--baseline-threshold", type=float, default=0.1,
        help="Relative throughput drop vs the baseline capture that "
             "flags regression=true (default 0.10)",
    )
    args = parser.parse_args(argv)

    if args.list_suites or args.suite:
        from benchmarks import run_suite, suite_names

        if args.list_suites:
            print("\n".join(suite_names()))
            return 0
        # Child-spawning suites (e.g. coldstart) clamp their timeouts to
        # what remains of this budget.
        from benchmarks._util import arm_deadline

        arm_deadline(
            args.deadline if args.deadline is not None else OVERALL_DEADLINE_S
        )
        return run_suite(args.suite)
    from music_analyst_tpu.observability import (
        install_flight_recorder,
        resolve_watchdog_timeout,
        start_watchdog,
    )

    # Same posture as the CLI: a crash leaves flight_record.json; the
    # watchdog arms only when $MUSICAAL_WATCHDOG_S is set.
    install_flight_recorder()
    start_watchdog(resolve_watchdog_timeout())
    payload = measure()
    if args.baseline:
        payload = _baseline_augment(args.baseline_threshold)(payload)
    print(json.dumps(payload))
    return 0


if __name__ == "__main__":
    sys.exit(main())
