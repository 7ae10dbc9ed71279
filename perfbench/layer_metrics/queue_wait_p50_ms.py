"""Median time a request waits in the batcher before its batch starts: the
batcher's own latency histogram (enqueue to settle, ``stats``) less the
median seconds of a batch (``serving.batch_seconds``), both p50."""


def read(artifacts):
    serve = artifacts.get("serve")
    if not serve:
        return None
    latency = serve["stats"]["requests"]["latency"].get("p50_s")
    batch = serve["histograms"].get("serving.batch_seconds", {}).get("p50_s")
    if latency is None or batch is None:
        return None
    return 1e3 * max(0.0, latency - batch)
