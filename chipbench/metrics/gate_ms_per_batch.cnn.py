"""Device milliseconds of the Zebra gates (ops under a ``zebra.<site>``
scope of the program) per batch classified in the traced window."""
from chipbench.metrics.lib import scopes


def read(data):
    batches = data["counters"].get("images", 0) / int(data["traffic"]["batch"])
    gate = scopes.cnn_gate_seconds(data) if batches > 0 else None
    return None if gate is None else 1e3 * gate / batches
