"""Share (%) of its roofline the encoder step reaches: the least time the
chip could take for the step's matrix multiplications (``flops.py``, from
the configuration's widths, rows per chip and sequence length, against the
published peak in ``peaks.json``) over the step's measured device time.
The step is compute-bound at these shapes; ``flops.roofline_seconds`` says
which bound it used."""

import common
import flops
from layer_metrics import encoder_step_ms


def read(artifacts):
    runs = encoder_step_ms.full_batch_runs(artifacts)
    if not runs:
        return None
    config = artifacts["config"]
    rows = artifacts["batch_size"] // artifacts["chips"]
    seq = config["model"]["max_len"]
    peaks = flops.load_peaks(artifacts["device"]["kind"])
    least = flops.roofline_seconds(
        flops.encoder_step_flops(config, rows, seq),
        flops.encoder_step_bytes(config, rows, seq), peaks)
    return 100.0 * least["seconds"] / common.median(runs)
