"""On the chip, once: the readings the Jamba decoder's tolerances are set
between.

    python3 perfbench/tools/mamba1_reference_probe.py [--seeds 2] [--rows 8]

For each seed: the corpus's first batch through the timed path at the
published widths; a sample of its rows judged by ``reference/jamba_f32.py``
(the bfloat16 system's reading: label scores, logits at the prompt's last
position, the Mamba-1 states and convolution tails and the attention layers'
keys and values after the prompt), and the reference computed with
``variant="int8"`` (the precision below the configuration's) and with
``variant="bf16_state"`` (the state carried in bfloat16), each judged the
same way against the float32 reference.  ``scan_state_max`` (the first
Mamba-1 layer's state against the float32 recurrence on the program's own
inputs to it) is read of the system and of that recurrence with its state
carried in bfloat16 (``bf16_scan``).  One JSON line a seed; ``--rehearsal``
runs the tiny preset on the CPU.
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
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--rows", type=int, default=8)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)

    config = common.load_json(
        os.path.join(BENCH_DIR, "configs", "ai21-jamba2-3b.json"))
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        config = common.with_rehearsal_overrides(config)
    import jax

    from music_analyst_tpu.engines.sentiment import get_backend
    from reference import jamba_f32 as reference

    common.require_devices(1, args.rehearsal)
    backend = get_backend(config["model"]["name"])
    rows_per_step = int(config["fixed"]["rows_per_chip"])
    labels = (backend._label_ids, backend._label_lens)
    rows_block = int(config["model"].get("reference_rows_block", 1))

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
        probe = {name: (np.asarray(value)[:len(sample)]
                        if name == "logits" or name.startswith("scan_")
                        else np.asarray(value)[:, :len(sample)])
                 for name, value in stats["probe"].items()}
        judged, seconds = {}, {}
        for variant in ("f32", "int8", "bf16_state"):
            t0 = time.monotonic()
            judged[variant] = reference.label_scores(
                backend.params, config, *sub, *labels, variant=variant,
                rows_block=rows_block)
            seconds[variant] = time.monotonic() - t0

        def as_system(variant):
            low = judged[variant]
            return reference.compare(
                judged["f32"], low["scores"],
                {**low["kept"], "logits": low["logits"]}, sub[1])

        bf16_scan = reference.scan_state(backend.params, config, probe,
                                         sub[1], "bfloat16")
        common.note(
            seed=seed, width=int(ids.shape[1]), rows=len(sample),
            tokens=int(np.asarray(lens).sum()), first_step_s=step_s,
            system={**reference.compare(judged["f32"], scores[sample], probe,
                                        sub[1]),
                    **reference.scan_readings(backend.params, config, probe,
                                              sub[1])},
            int8=as_system("int8"), bf16_state=as_system("bf16_state"),
            bf16_scan=reference.scan_readings(
                backend.params, config, {**probe, "state": bf16_scan[None]},
                sub[1]),
            reference_s=seconds, device=common.device_report(
                jax.devices()[:1]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
