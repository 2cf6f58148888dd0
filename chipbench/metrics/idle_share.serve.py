"""Share of the traced serving round in which no operation ran on the
device, in percent."""
from chipbench.metrics.lib import trace as tr


def read(data):
    w = tr.window_seconds(data["trace"])
    return 100.0 * (1.0 - tr.busy_seconds(data["trace"]) / w) if w > 0 else None
