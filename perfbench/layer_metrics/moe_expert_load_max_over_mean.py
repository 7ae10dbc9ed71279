"""How uneven the routing is: per scoring step and routed layer, the most
assignments one expert received over the mean an expert received (the
program's device-side reduction, returned with the scores and recorded on
the step's ``compute`` span; padding positions are routed too and count).
Median over a job's steps and layers, then over jobs.  1.0 is even; the
grouped matmul does the same work at any value, an expert-parallel layout
would wait for the fullest expert."""

import common
import job_spans


def job_ratio(log):
    ratios = [r for s in job_spans.named(log, "compute")
              for r in s["attrs"].get("expert_load_max_over_mean", ())]
    return common.median(ratios) if ratios else None


def read(artifacts):
    return job_spans.median_over_jobs(artifacts, job_ratio)
