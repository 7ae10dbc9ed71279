"""Share (%) of its roofline the Mamba-2 prefill recurrence reaches over the
traced job (``ops/ssd_scan.py``; its executions are the device operations
whose name holds ``KERNEL``): the least time the chip could take for the
selective scan over the real prompt tokens of every Mamba-2 layer
(``flops_granite.ssd_prefill_flops`` / ``_bytes`` over ``peaks.json``) over
those operations' summed device time: the same work whatever implements
it."""

import flops
import flops_granite
from layer_metrics import ssm_step_mfu

KERNEL = "_ssd_"


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
    steps = ssm_step_mfu.traced_steps(artifacts)
    if not seconds or not steps:
        return None
    config = artifacts["config"]
    peaks = flops.load_peaks(artifacts["device"]["kind"])
    least = flops.roofline_seconds(
        sum(flops_granite.ssd_prefill_flops(config, s) for s in steps),
        sum(flops_granite.ssd_prefill_bytes(config, s) for s in steps),
        peaks)["seconds"]
    return 100.0 * least / seconds
