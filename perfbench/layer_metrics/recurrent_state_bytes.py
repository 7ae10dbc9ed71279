"""Bytes of recurrent state a scoring step holds beside the latent cache:
the manifest's gauge ``recurrent_state_bytes`` (rows x KDA layers x heads x
a float32 ``d x d`` state and the convolutions' last inputs).  Median over
jobs; a program without such layers records none."""

import common


def read(artifacts):
    sizes = []
    for job in artifacts.get("jobs", ()):
        part = job["parts"].get("sentiment")
        gauges = ((part and part.get("manifest")) or {}).get("gauges", {})
        if gauges.get("recurrent_state_bytes"):
            sizes.append(gauges["recurrent_state_bytes"])
    return common.median(sizes) if sizes else None
