"""Share (%) of its roofline the Mamba-1 prefill recurrence reaches over the
traced job (``ops/selective_scan.py``; its executions are the device
operations whose name holds ``KERNEL``): the least time the chip could take
for the step's product ``r W_dt`` and the selective scan over the real prompt
tokens of every Mamba-1 layer (``flops_jamba.selective_scan_flops`` /
``_bytes`` over ``peaks.json``) over those operations' summed device time:
the same work whatever implements it."""

import flops
import flops_jamba
from layer_metrics import mamba1_step_mfu

KERNEL = "_selective_scan"


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
    steps = mamba1_step_mfu.traced_steps(artifacts)
    if not seconds or not steps:
        return None
    config = artifacts["config"]
    peaks = flops.load_peaks(artifacts["device"]["kind"])
    least = flops.roofline_seconds(
        sum(flops_jamba.selective_scan_flops(config, s) for s in steps),
        sum(flops_jamba.selective_scan_bytes(config, s) for s in steps),
        peaks)["seconds"]
    return 100.0 * least / seconds
