"""Model FLOPs of every token the traced round processed (prefilled,
teacher-forced and generated; logits where the program computes them)
over the round's seconds and the chip's peak, in percent."""


def read(data):
    c, peak = data["counters"], data["peak"]
    if not c.get("model_flops") or not c.get("window_s"):
        return None
    return 100.0 * c["model_flops"] / (c["window_s"] * peak["flops_per_s"])
