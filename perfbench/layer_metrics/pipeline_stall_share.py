"""Share (%) of a ``sentiment`` job in which the device-side consumer of the
prefetch pipeline waited for a batch: the sink's ``stall_s`` in the
manifest's ``pipeline`` section over the job's seconds.  Median over jobs.
High means the data plane (read, tokenize, host-to-device) sets the pace."""

import common


def read(artifacts):
    shares = []
    for job in artifacts.get("jobs", ()):
        part = job["parts"].get("sentiment")
        manifest = part and part.get("manifest")
        if not manifest:
            continue
        stages = manifest.get("pipeline", {}).get("pipeline", {}).get("stages")
        if stages:
            shares.append(100.0 * stages[-1]["stall_s"] / part["seconds"])
    return common.median(shares) if shares else None
