"""Roofline share of the Zebra producer (``kernels/mask_pack.py``): the
least time its contract needs over every prefill call of the traced round
(read each FFN hidden map once, write its live blocks and the bitmap) over
the device time of its two Pallas kernels (custom calls named after
``zebra_mask_pack``, the function that launches them), in percent."""
from chipbench.metrics.lib import counts
from chipbench.metrics.lib import trace as tr

KERNELS = ("zebra_mask_pack",)      # its comparator and pack kernels


def read(data):
    c, cfg = data["counters"], data["config"]
    z = cfg["served"]["zebra"]
    t = tr.kernel_seconds(data["trace"], KERNELS)
    if not c.get("prefill_calls") or t <= 0:
        return None
    K, bs, bc = cfg["intermediate_size"], z["block_seq"], z["block_ch"]
    least = 0.0
    for call in c["prefill_calls"]:
        ops, byt = counts.mask_pack_cost(call["M"] * c["layers"], K,
                                         call["n_live"], bs, bc, 2)
        least += counts.roofline_seconds(ops, byt, data["peak"])[0]
    return 100.0 * least / t
