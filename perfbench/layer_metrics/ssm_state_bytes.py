"""Bytes of recurrent state a state-space scoring step holds beside the
attention layer's key/value cache: the manifest's gauge
``recurrent_state_bytes`` (rows x Mamba-2 layers x (heads x a float32 ``P x
N`` state + the convolution's last inputs)) of the jobs whose program also
records ``ssm.tokens``.  Median over jobs; a program without such layers
records none.  (``recurrent_state_bytes`` reads the same gauge in the cell
that had it first; its list of cells is the accepted benchmark's.)"""

import common


def read(artifacts):
    sizes = []
    for job in artifacts.get("jobs", ()):
        part = job["parts"].get("sentiment")
        manifest = (part and part.get("manifest")) or {}
        size = manifest.get("gauges", {}).get("recurrent_state_bytes")
        if size and manifest.get("counters", {}).get("ssm.tokens"):
            sizes.append(size)
    return common.median(sizes) if sizes else None
