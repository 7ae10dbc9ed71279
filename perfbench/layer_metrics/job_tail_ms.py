"""The tail of a ``sentiment`` job (ms): from the end of its last
``compute`` span, when the device has nothing left of the job, to the end
of the ``manifest`` span (last rows written, totals, manifest).  The chip
idles through it.  Median over jobs."""

import job_spans


def read(artifacts):
    return job_spans.median_over_jobs(artifacts, job_spans.tail_ms)
