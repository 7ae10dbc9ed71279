"""Share (%) of the chip's bfloat16 peak the zero-shot decoder's scoring
program reaches over the traced job: the matrix operations of every step
that ran (``flops_decoder.step_flops``: from the configuration's widths and
the real token counts the program put on each ``compute`` span, padding and
the masked triangle not counted) over the published peak (``peaks.json``)
and the summed device time of the program's executions in the trace.  The
share of the whole step, the same work whatever implements it."""

import os

import flops
import flops_decoder
import job_spans
import trace_reduce

MODULE = "score_labels"


def traced_steps(artifacts):
    """The ``compute`` spans of the traced job (the run's first) that carry
    the step's token counts; none from a program that records none."""
    jobs = artifacts.get("jobs") or ()
    part = jobs[0]["parts"].get("sentiment") if jobs else None
    log = part and job_spans.read_log(
        os.path.join(part["dir"], "telemetry.jsonl"))
    if not log:
        return []
    return [s["attrs"] for s in job_spans.named(log, "compute")
            if "token_pairs" in s["attrs"]]


def read(artifacts):
    if not artifacts.get("trace"):
        return None
    runs = trace_reduce.module_runs(artifacts["trace"], MODULE)
    steps = traced_steps(artifacts)
    if not runs or not steps:
        return None
    work = sum(flops_decoder.step_flops(artifacts["config"], s) for s in steps)
    peak = flops.load_peaks(artifacts["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * work / peak / sum(runs)
