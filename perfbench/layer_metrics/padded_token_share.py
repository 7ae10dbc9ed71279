"""Share (%) of the positions a ``sentiment`` job's scoring steps computed
that hold padding: one minus the manifest's counter ``decoder.tokens_real``
over ``decoder.tokens_computed`` (prompt positions up to the batch's width,
and the label continuations' positions).  Median over jobs.  High means the
batch width rule, not the lyrics, sets the step's work."""

import common


def read(artifacts):
    shares = []
    for job in artifacts.get("jobs", ()):
        part = job["parts"].get("sentiment")
        counters = ((part and part.get("manifest")) or {}).get("counters", {})
        computed = counters.get("decoder.tokens_computed")
        if computed:
            shares.append(100.0 * (1.0 - counters["decoder.tokens_real"]
                                   / computed))
    return common.median(shares) if shares else None
