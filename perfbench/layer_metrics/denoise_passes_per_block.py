"""Denoising passes a block took: the manifest's counter
``diffusion.denoise_passes`` over ``diffusion.blocks`` (both of the batch: a
block's passes run until no row has a mask left).  Median over jobs.
``block_length`` under weights whose confidences never reach the threshold;
a trained model's confidences lower it, and a step's time with it."""

import common


def read(artifacts):
    ratios = []
    for job in artifacts.get("jobs", ()):
        part = job["parts"].get("sentiment")
        counters = ((part and part.get("manifest")) or {}).get("counters", {})
        blocks = counters.get("diffusion.blocks")
        if blocks:
            ratios.append(counters["diffusion.denoise_passes"] / blocks)
    return common.median(ratios) if ratios else None
