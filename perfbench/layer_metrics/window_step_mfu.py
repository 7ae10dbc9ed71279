"""Share (%) of the chip's bfloat16 peak the scoring program of the decoder
whose attention layers differ in kind reaches over the traced job: the
operations of every step that ran (``flops_laguna.step_flops``: from the
configuration's widths and the real counts the program put on each
``compute`` span; padding, fillers, the pairs outside a layer's mask and the
experts the other chip holds not counted) over the published peak
(``peaks.json``) and the summed device time of the program's executions in
the trace.  The share of the whole step, the same work whatever implements
it."""

import os

import flops
import flops_laguna
import job_spans
from layer_metrics.hybrid_step_mfu import program_seconds  # noqa: F401


def traced_steps(artifacts):
    """The ``compute`` spans of the traced job (the run's first) that carry
    such a step's counts; none from a program that records none."""
    jobs = artifacts.get("jobs") or ()
    part = jobs[0]["parts"].get("sentiment") if jobs else None
    log = part and job_spans.read_log(
        os.path.join(part["dir"], "telemetry.jsonl"))
    if not log:
        return []
    return [s["attrs"] for s in job_spans.named(log, "compute")
            if "attention_layers_window" in s["attrs"]
            and "assignments_held" in s["attrs"]]


def read(artifacts):
    seconds = program_seconds(artifacts)
    steps = traced_steps(artifacts)
    if not seconds or not steps:
        return None
    work = sum(flops_laguna.step_flops(artifacts["config"], s)
               for s in steps)
    peak = flops.load_peaks(artifacts["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * work / peak / seconds
