"""Forward FLOPs per image (convolutions and classifier) times the images
classified in the traced window, over the window's seconds and the chip's
peak, in percent."""
from chipbench.metrics.lib import counts


def read(data):
    c, peak = data["counters"], data["peak"]
    if not c.get("images") or not c.get("window_s"):
        return None
    flops = counts.resnet_flops_per_image(data["config"]) * c["images"]
    return 100.0 * flops / (c["window_s"] * peak["flops_per_s"])
