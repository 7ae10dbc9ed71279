"""Share (%) of its roofline the prefill's attention reaches over the traced
job, full and sliding-window layers together (``ops/flash_attention.py``; its
executions are the device operations whose name holds ``KERNEL``): the least
time the chip could take for the real in-mask (query, key) pairs of every
layer (``flops_laguna.attention_flops`` of the prefill, causal pairs on a
full layer and of those the pairs inside the window on a sliding one;
``attention_prefill_bytes``; over ``peaks.json``) over those operations'
summed device time: the same work whatever implements it."""

import flops
import flops_laguna
from layer_metrics import window_step_mfu

KERNEL = "_flash_call"


def kernel_seconds(artifacts):
    """Summed device time of the operations named ``KERNEL``, or None."""
    trace = artifacts.get("trace")
    if not trace:
        return None
    first = trace["devices"][sorted(trace["devices"])[0]]
    seconds = sum(t for name, t in first["op_s"].items() if KERNEL in name)
    return seconds or None


def read(artifacts):
    seconds = kernel_seconds(artifacts)
    steps = window_step_mfu.traced_steps(artifacts)
    if not seconds or not steps:
        return None
    config = artifacts["config"]
    peaks = flops.load_peaks(artifacts["device"]["kind"])
    least = flops.roofline_seconds(
        sum(flops_laguna.attention_flops(config, s, labels=False)
            for s in steps),
        sum(flops_laguna.attention_prefill_bytes(config, s) for s in steps),
        peaks)["seconds"]
    return 100.0 * least / seconds
