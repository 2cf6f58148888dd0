"""Share of the device's busy time in the traced inference window spent
in ops that run any Zebra gate instruction, fused into another layer's
op or not, each op counted whole, in percent: a ceiling on the gates'
time, where ``gate_share.cnn`` is its floor."""
from chipbench.metrics.lib import scopes
from chipbench.metrics.lib import trace as tr


def read(data):
    gate = scopes.cnn_gate_seconds(data, fused=True)
    busy = tr.busy_seconds(data["trace"])
    if gate is None or busy <= 0:
        return None
    return 100.0 * gate / busy
