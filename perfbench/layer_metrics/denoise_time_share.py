"""Share (%) of a block-diffusion step's device time that the block loop
takes (denoising and commit passes, ``jit__diffusion_denoise``) against the
step's two programs together (with the prefill,
``jit__diffusion_prefill``), over the traced job.  High means generation,
bound by reading the weights once a pass, sets the rate; low means the
prefill does."""

from layer_metrics import diffusion_step_mfu


def read(artifacts):
    seconds = diffusion_step_mfu.program_seconds(artifacts)
    if not seconds:
        return None
    prefill, denoise = seconds
    return 100.0 * denoise / (prefill + denoise)
