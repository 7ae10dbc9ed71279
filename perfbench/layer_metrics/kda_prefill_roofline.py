"""Share (%) of its roofline the KDA prefill recurrence reaches over the
traced job (``ops/kda_attention.py``; its executions are the device
operations whose name holds ``KERNEL``): the least time the chip could take
for the gated delta rule over the real prompt tokens of every KDA layer
(``flops_ling.kda_prefill_flops`` / ``_bytes`` over ``peaks.json``) over
those operations' summed device time: the same work whatever implements
it."""

import flops
import flops_ling
from layer_metrics import hybrid_step_mfu

KERNEL = "_kda_"


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
    steps = hybrid_step_mfu.traced_steps(artifacts)
    if not seconds or not steps:
        return None
    config = artifacts["config"]
    peaks = flops.load_peaks(artifacts["device"]["kind"])
    least = flops.roofline_seconds(
        sum(flops_ling.kda_prefill_flops(config, s) for s in steps),
        sum(flops_ling.kda_prefill_bytes(config, s) for s in steps),
        peaks)["seconds"]
    return 100.0 * least / seconds
