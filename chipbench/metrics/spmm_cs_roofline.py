"""Roofline share of the Zebra consumer (``kernels/spmm_cs.py``, the FFN
down projection from the compressed stream): the least time its contract
needs over every prefill call of the traced round over the device time of
its Pallas kernel, in percent."""
from chipbench.metrics.lib import counts
from chipbench.metrics.lib import trace as tr

KERNELS = ("zebra_spmm_cs",)


def read(data):
    c, cfg = data["counters"], data["config"]
    z = cfg["served"]["zebra"]
    t = tr.kernel_seconds(data["trace"], KERNELS)
    if not c.get("prefill_calls") or t <= 0:
        return None
    K, N = cfg["intermediate_size"], cfg["hidden_size"]
    bs, bc, L = z["block_seq"], z["block_ch"], c["layers"]
    least = 0.0
    for call in c["prefill_calls"]:
        ops, byt = counts.spmm_cs_cost(call["M"], K, N, call["n_live"] / L,
                                       bs, bc, 2)
        least += L * counts.roofline_seconds(ops, byt, data["peak"])[0]
    return 100.0 * least / t
