"""On the chip, once: the two readings the block-diffusion decoder's
tolerances are set between.

    python3 perfbench/tools/diffusion_reference_probe.py [--seeds 3] [--rows 8]

For each seed: the corpus's first batch through the timed path at the
published widths, the committed caches kept; a sample of its rows judged by
``reference/sdar_moe_f32.py`` with the step's own choices handed over under
generous margins (the bfloat16 system's reading: how deep the ties of
experts, tokens and positions lie, the log-probability and cache
differences), and the reference computed with ``variant="int8"`` judged the
same way (the reading of the precision below); ``--limits`` judges both
under the limits the cell uses.  One JSON line a seed; ``--rehearsal`` runs
the tiny preset on the CPU.
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
    parser.add_argument("--route-margin", type=float, default=0.01)
    parser.add_argument(
        "--limits", action="store_true",
        help="judge under the reference's TOLERANCE as the cell does, not "
             "under the generous margins the ties are read with")
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)

    config = common.load_json(
        os.path.join(BENCH_DIR, "configs", "sdar-30b-a3b-chat.json"))
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        config = common.with_rehearsal_overrides(config)
    import jax

    from drivers.batch_job_diffusion import SAMPLER
    from music_analyst_tpu.engines.sentiment import get_backend
    from reference import sdar_moe_f32 as reference

    common.require_devices(1, args.rehearsal)
    backend = get_backend(config["model"]["name"])
    sampler = {key: config["model"][key] for key in SAMPLER}
    rows_per_step = int(config["fixed"]["rows_per_chip"])
    wide = (reference.TOLERANCE if args.limits else dict(
        reference.TOLERANCE, route_margin=args.route_margin,
        token_margin=1.0, confidence_margin=1.0))

    for seed in range(args.seeds):
        csv_path = corpus.ensure_corpus(
            common.OUT_ROOT, config["corpus"]["generator"], 1000 + seed)
        first = [row[3] for row in corpus.read_rows(
            csv_path, limit=rows_per_step)]
        prepared = backend.prepare(first)
        _, ids, lens = prepared
        t0 = time.monotonic()
        handle = backend.launch(backend.transfer(prepared), keep_caches=True)
        jax.block_until_ready(handle[1])
        step_s = time.monotonic() - t0
        sample = np.sort(np.random.default_rng(seed).choice(
            len(first), size=min(args.rows, len(first)), replace=False))
        system = reference.system_rows(handle[1], handle[2], sample)
        passes = np.asarray(handle[1]["denoise_passes"]).tolist()
        del handle
        sub = (np.asarray(ids)[sample], np.asarray(lens)[sample])
        readings = {}
        for variant in ("f32", "int8"):
            t0 = time.monotonic()
            judged = reference.judge(backend.params, config, sampler, *sub,
                                     system, variant, wide)
            judged.pop("tolerance")
            judged["seconds"] = time.monotonic() - t0
            readings[variant] = judged
        common.note(
            seed=seed, width=int(ids.shape[1]), rows=len(sample),
            lens=sub[1].tolist(), denoise_passes=passes,
            first_step_s=step_s, system=readings["f32"],
            int8=readings["int8"],
            device=common.device_report(jax.devices()[:1]),
            bytes_limit=(jax.devices()[0].memory_stats() or {}).get(
                "bytes_limit"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
