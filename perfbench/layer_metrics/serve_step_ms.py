"""Device time of one served batch: the median length of the executions of
the jitted forward program in the traced seconds of a serve window."""

import common
import trace_reduce
from layer_metrics import encoder_step_ms


def read(artifacts):
    if not artifacts.get("serve") or not artifacts.get("trace"):
        return None
    runs = trace_reduce.module_runs(artifacts["trace"], encoder_step_ms.MODULE)
    return 1e3 * common.median(runs) if runs else None
