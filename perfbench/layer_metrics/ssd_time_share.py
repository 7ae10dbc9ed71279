"""Share (%) of the state-space hybrid scoring program's device time that
the Mamba-2 prefill recurrence takes (the device operations named ``_ssd_``)
over the traced job.  High means the recurrence, not the projections and
experts around it, sets the rate."""

from layer_metrics import ssd_prefill_roofline, ssm_step_mfu


def read(artifacts):
    kernel = ssd_prefill_roofline.kernel_seconds(artifacts)
    program = ssm_step_mfu.program_seconds(artifacts)
    if not kernel or not program:
        return None
    return 100.0 * kernel / program
