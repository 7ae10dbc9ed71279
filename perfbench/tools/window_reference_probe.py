"""On the chip, once: the readings the window decoder's tolerances are set
between, each through the comparison that decides the cell's ``correct``.

    python3 perfbench/tools/window_reference_probe.py [--seeds 2]

For each seed the corpus's first batch goes through the timed path at the
published widths, a sample of its rows is drawn as the cell's driver draws
it (most of them longer than the window), and ``drivers/batch_job_window``'s
own ``against_reference`` / ``judge`` (the cell's limits, the step's expert
choices handed over) gives ``correct`` and the limits that failed for:

* ``system``: the bfloat16 system against the float32 reference: the one
  reading that has to come out ``correct``;
* ``int8``: the same step against the reference computed with
  ``variant="int8"`` (every projection and expert matmul fake-quantized):
  the precision below.  The system's own ``quant="int8"`` is not the
  control: it quantizes the attention projections and the dense SwiGLU and
  leaves ``RoutedMoE``'s grouped matmuls, 87% of this chip's
  weights, in bfloat16;
* three wrong programs made IN THE SYSTEM (``--controls``): the same
  parameters under a configuration with one part of the mathematics left
  out (``window``: the sliding layers run causal; ``yarn_factor``: cos and
  sin without YaRN's ``attention_factor``; ``gate``: no gate on the
  attention output), each compiled and run as the timed step is, against
  the float32 reference of the configuration as published.

One JSON line a seed; exit code 1 where ``system`` is not ``correct`` or a
control is.  ``--rehearsal`` runs the tiny preset on the CPU.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import common  # noqa: E402
import corpus  # noqa: E402


def wrong_system(backend, part: str):
    """``backend`` with ``part`` of the mathematics left out: its own
    parameters and tokenizer under a changed configuration, the scoring
    program built as the classifier builds it."""
    from music_analyst_tpu.models import llama

    cfg = backend.config

    def kinds(change):
        return tuple((name, change(kind))
                     for name, kind in cfg.attention_kinds)

    if part == "window":
        cfg = dataclasses.replace(cfg, attention_kinds=kinds(
            lambda kind: dataclasses.replace(kind, window=0)))
    elif part == "yarn_factor":
        cfg = dataclasses.replace(cfg, attention_kinds=kinds(
            lambda kind: kind if kind.yarn is None else dataclasses.replace(
                kind, yarn=tuple(sorted(
                    {**dict(kind.yarn), "attention_factor": 1.0}.items())))))
    elif part == "gate":
        cfg = dataclasses.replace(cfg, gqa_output_gate="none")
    else:
        raise SystemExit(f"window_reference_probe: no control {part!r}")
    wrong = copy.copy(backend)
    wrong.config = cfg
    wrong.model = llama.LlamaModel(cfg)
    wrong._score_labels = llama.score_labels_program(wrong.model, cfg, None)
    return wrong


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--controls", default="window,yarn_factor,gate",
                        help="parts left out of the system, one a reading")
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)

    config = common.load_json(
        os.path.join(BENCH_DIR, "configs", "laguna-s-2.1.json"))
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        config = common.with_rehearsal_overrides(config)
    import jax

    from drivers.batch_job_window import (
        _sample,
        against_reference,
        first_step,
    )
    from music_analyst_tpu.engines.sentiment import get_backend
    from reference import laguna_f32 as reference

    common.require_devices(1, args.rehearsal)
    model = config["model"]
    tol = reference.TEST_TOLERANCE if args.rehearsal else reference.TOLERANCE
    backend = get_backend(model["name"])
    systems = {"system": backend, **{
        "without_" + part: wrong_system(backend, part)
        for part in args.controls.split(",") if part}}
    rows_per_step = int(config["fixed"]["rows_per_chip"])
    rc = 0

    def peak():
        return (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use")

    for seed in range(args.seeds):
        csv_path = corpus.ensure_corpus(
            common.OUT_ROOT, config["corpus"]["generator"], 1000 + seed)
        texts = [row[3] for row in corpus.read_rows(
            csv_path, limit=rows_per_step)]
        readings = {}
        for name, system in systems.items():
            t0 = time.monotonic()
            sample, step = first_step(
                system, texts, lambda lens: _sample(seed, lens, model))
            step_s = time.monotonic() - t0
            variants = ("f32", "int8") if name == "system" else ("f32",)
            for variant in variants:
                t0 = time.monotonic()
                read = against_reference(
                    backend, config, tol, step, sample, variant)
                read.pop("tolerance")
                key = name if variant == "f32" else variant
                readings[key] = {
                    "correct": read.pop("ok"), **read, "step_s": step_s,
                    "reference_s": time.monotonic() - t0,
                    "peak_bytes": peak()}
                rc |= readings[key]["correct"] != (key == "system")
        common.note(seed=seed, tolerance=tol, **readings,
                    device=common.device_report(jax.devices()[:1]))
    return rc


if __name__ == "__main__":
    sys.exit(main())
