"""Share (%) of the hybrid scoring program's device time that the KDA
prefill recurrence takes (the device operations named ``_kda_``) over the
traced job.  High means the recurrence, not the projections and experts
around it, sets the rate."""

from layer_metrics import hybrid_step_mfu, kda_prefill_roofline


def read(artifacts):
    kernel = kda_prefill_roofline.kernel_seconds(artifacts)
    program = hybrid_step_mfu.program_seconds(artifacts)
    if not kernel or not program:
        return None
    return 100.0 * kernel / program
