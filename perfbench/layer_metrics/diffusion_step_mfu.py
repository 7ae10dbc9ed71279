"""Share (%) of the chip's bfloat16 peak the block-diffusion decoder's step
reaches over the traced job: the matrix operations of every step that ran
(``flops_sdar.step_flops``: from the configuration's widths and the real
counts the program put on each ``compute`` span; padding, the masked part
of attention and clean positions' logits not counted) over the published
peak (``peaks.json``) and the summed device time of the step's two programs
(the prefill and the block loop) in the trace.  The share of the whole
step, the same work whatever implements it."""

import os

import flops
import flops_sdar
import job_spans
import trace_reduce

PREFILL, DENOISE = "diffusion_prefill", "diffusion_denoise"


def traced_steps(artifacts):
    """The ``compute`` spans of the traced job (the run's first) that carry
    a block-diffusion step's counts; none from a program that records
    none."""
    jobs = artifacts.get("jobs") or ()
    part = jobs[0]["parts"].get("sentiment") if jobs else None
    log = part and job_spans.read_log(
        os.path.join(part["dir"], "telemetry.jsonl"))
    if not log:
        return []
    return [s["attrs"] for s in job_spans.named(log, "compute")
            if "denoise_passes" in s["attrs"]]


def program_seconds(artifacts):
    """``(prefill, denoise)`` device seconds of the traced job, or ``None``
    where the trace holds neither program."""
    if not artifacts.get("trace"):
        return None
    prefill = trace_reduce.module_runs(artifacts["trace"], PREFILL)
    denoise = trace_reduce.module_runs(artifacts["trace"], DENOISE)
    if not prefill or not denoise:
        return None
    return sum(prefill), sum(denoise)


def read(artifacts):
    seconds = program_seconds(artifacts)
    steps = traced_steps(artifacts)
    if not seconds or not steps:
        return None
    work = sum(flops_sdar.step_flops(artifacts["config"], s) for s in steps)
    peak = flops.load_peaks(artifacts["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * work / peak / sum(seconds)
