"""Share (%) of its roofline the prefill's block-causal attention kernel
reaches over the traced job (``ops/flash_attention.py`` with
``block_causal``; its executions are the device operations whose name holds
``KERNEL``): the least time the chip could take for the real block-causal
pairs and the prefilled positions' queries, keys, values and outputs
(``flops_sdar.block_causal_attention_flops`` / ``_bytes`` over
``peaks.json``) over the kernel's summed device time."""

import flops
import flops_sdar
from layer_metrics import diffusion_step_mfu

KERNEL = "_flash_call"


def read(artifacts):
    trace = artifacts.get("trace")
    steps = diffusion_step_mfu.traced_steps(artifacts)
    if not trace or not steps:
        return None
    first = trace["devices"][sorted(trace["devices"])[0]]
    seconds = sum(t for name, t in first["op_s"].items() if KERNEL in name)
    if not seconds:
        return None
    config = artifacts["config"]
    peaks = flops.load_peaks(artifacts["device"]["kind"])
    least = flops.roofline_seconds(
        sum(flops_sdar.block_causal_attention_flops(config, s) for s in steps),
        sum(flops_sdar.block_causal_attention_bytes(config, s) for s in steps),
        peaks)["seconds"]
    return 100.0 * least / seconds
