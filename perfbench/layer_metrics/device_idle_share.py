"""Share (%) of the traced window in which no operation ran on the device,
from the device trace alone; on several chips, the worst chip (mean and
worst are both on an earlier line of the run)."""


def read(artifacts):
    trace = artifacts.get("trace")
    return 100.0 * trace["idle_share_worst"] if trace else None
