"""Share (%) of the Jamba scoring program's device time that the Mamba-1
prefill recurrence takes (the device operations named
``_selective_scan``) over the traced job.  High means the recurrence, not
the projections and SwiGLUs around it, sets the rate."""

from layer_metrics import mamba1_step_mfu, selective_scan_roofline


def read(artifacts):
    kernel = selective_scan_roofline.kernel_seconds(artifacts)
    program = mamba1_step_mfu.program_seconds(artifacts)
    if not kernel or not program:
        return None
    return 100.0 * kernel / program
