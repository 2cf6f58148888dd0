"""Share of the device's busy time in the traced inference window spent
in the Zebra gates (ops under a ``zebra.<site>`` scope of the program),
in percent."""
from chipbench.metrics.lib import scopes
from chipbench.metrics.lib import trace as tr


def read(data):
    gate = scopes.cnn_gate_seconds(data)
    busy = tr.busy_seconds(data["trace"])
    if gate is None or busy <= 0:
        return None
    return 100.0 * gate / busy
