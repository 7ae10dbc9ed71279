"""The head of a ``sentiment`` job (ms): from the program's ``run_start``
event to the end of the first batch's ``h2d`` span, when the first program
is on the device's queue.  The chip idles through it.  Median over jobs."""

import job_spans


def read(artifacts):
    return job_spans.median_over_jobs(artifacts, job_spans.head_ms)
