"""Songs a second through the host ingest of ``analyze`` (the program's own
``ingest`` stage seconds in ``performance_metrics.json``), median over the
jobs of the run."""

import common


def read(artifacts):
    rates = []
    for job in artifacts.get("jobs", ()):
        part = job["parts"].get("analyze")
        if part and part.get("timings", {}).get("ingest"):
            rates.append(part["songs"] / part["timings"]["ingest"])
    return common.median(rates) if rates else None
