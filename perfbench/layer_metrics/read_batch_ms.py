"""Host time of one full-batch read of the CSV source: the median length
(ms) of a ``sentiment`` job's ``read`` spans (the prefetch pipeline's, one
around each ``next()`` of its source) at the full batch.  Median over jobs.
Times batches a job, against ``encoder_step_ms`` times steps a job, it says
how far the reader is from setting the pace."""

import job_spans


def read(artifacts):
    return job_spans.median_over_jobs(artifacts, job_spans.read_batch_ms)
