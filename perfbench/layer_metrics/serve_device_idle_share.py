"""``device_idle_share`` over the traced seconds of a serve window (a metric
has one ``moves``, and in a serve cell idle time moves the latency tail)."""

from layer_metrics import device_idle_share


def read(artifacts):
    return device_idle_share.read(artifacts) if artifacts.get("serve") else None
