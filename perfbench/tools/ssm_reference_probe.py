"""On the chip, once: the two readings the state-space hybrid decoder's
tolerances are set between.

    python3 perfbench/tools/ssm_reference_probe.py [--seeds 3] [--rows 8]

For each seed: the corpus's first batch through the timed path at the
published widths; a sample of its rows judged by ``reference/
granite_hybrid_f32.py`` with the step's own expert choices handed over (the
bfloat16 system's reading: choices that differ, how deep the ties lie,
wrong choices at ``--margin``, label-score differences, the Mamba-2 states
and convolution tails and the attention layer's keys and values after the
prompt), and the reference computed with
``variant="int8"`` judged the same way against the same step (the reading
of the precision below).  One JSON line a seed; ``--rehearsal`` runs the
tiny preset on the CPU.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

import common  # noqa: E402
import corpus  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", type=int, default=3)
    parser.add_argument("--rows", type=int, default=8)
    parser.add_argument("--margin", type=float, default=0.05)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)

    config = common.load_json(
        os.path.join(BENCH_DIR, "configs", "granite-4.0-h-small.json"))
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        config = common.with_rehearsal_overrides(config)
    import jax

    from music_analyst_tpu.engines.sentiment import get_backend
    from reference import granite_hybrid_f32 as reference

    common.require_devices(1, args.rehearsal)
    backend = get_backend(config["model"]["name"])
    rows_per_step = int(config["fixed"]["rows_per_chip"])
    labels = (backend._label_ids, backend._label_lens)

    for seed in range(args.seeds):
        csv_path = corpus.ensure_corpus(
            common.OUT_ROOT, config["corpus"]["generator"], 1000 + seed)
        first = [row[3] for row in corpus.read_rows(
            csv_path, limit=rows_per_step)]
        sample = np.sort(np.random.default_rng(seed).choice(
            len(first), size=min(args.rows, len(first)), replace=False))
        backend.probe_rows = np.resize(sample, (8,)).astype(np.int32)
        prepared = backend.prepare(first)
        _, ids, lens = prepared
        t0 = time.monotonic()
        handle = backend.launch(backend.transfer(prepared))
        scores, stats = np.asarray(handle[1], np.float64), handle[2]
        step_s = time.monotonic() - t0
        sub = (np.asarray(ids)[sample], np.asarray(lens)[sample])
        prefer = reference.prefer_from_system(
            np.asarray(stats["chosen"])[:, sample],
            np.asarray(stats["chosen_labels"])[:, :, sample], sub[1])
        probe = {name: np.asarray(value)[:, :len(sample)]
                 for name, value in stats["probe"].items()}

        def reading(variant):
            t0 = time.monotonic()
            judged = reference.label_scores(
                backend.params, config, *sub, *labels, variant=variant,
                prefer=prefer, margin=args.margin)
            diff = np.abs(scores[sample] - judged["scores"])
            return {"max": float(diff.max()),
                    "median": float(np.median(diff)), **judged["routing"],
                    **reference.compare_kept(judged["kept"], probe, sub[1]),
                    "seconds": time.monotonic() - t0}

        common.note(
            seed=seed, width=int(ids.shape[1]), rows=len(sample),
            margin=args.margin, first_step_s=step_s,
            system=reading("f32"), int8=reading("int8"),
            device=common.device_report(jax.devices()[:1]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
