"""Share (%) of the (query, key) pairs the prefill's attention computed that
lie outside the mask, over a ``sentiment`` job's scoring steps: one minus
``token_pairs`` (the real pairs inside each layer's mask, causal on a full
layer and inside the window on a sliding one, summed over the layers) over
``token_pairs_tiles`` (the pairs in the tiles of the kernel's grid that ran,
summed over the layers: ``ops/flash_attention.visited_pairs``), both from the
``compute`` spans the program wrote.  What the kernel computes outside the
mask: padding behind a row inside its last tile, the triangle's and the
window's edges, tiles behind the window where they are not skipped.  Median
over jobs."""

import os

import common
import job_spans


def read(artifacts):
    shares = []
    for job in artifacts.get("jobs", ()):
        part = job["parts"].get("sentiment")
        log = part and job_spans.read_log(
            os.path.join(part["dir"], "telemetry.jsonl"))
        if not log:
            continue
        steps = [s["attrs"] for s in job_spans.named(log, "compute")
                 if s["attrs"].get("token_pairs_tiles")]
        if steps:
            shares.append(100.0 * (1.0 - sum(s["token_pairs"] for s in steps)
                                   / sum(s["token_pairs_tiles"]
                                         for s in steps)))
    return common.median(shares) if shares else None
