"""Model FLOPs of the traced round's prefills (each call's length through
``counts.lm_prefill_flops``) over the device time of the prefill
program's executions and the chip's peak, in percent: the whole prefill
step's share of the peak, which bounds the Zebra kernels' rooflines."""
from chipbench.metrics.lib import counts
from chipbench.metrics.lib import trace as tr

PROGRAM = "jit_prefill"


def read(data):
    calls = data["counters"].get("prefill_calls")
    t = sum(b - a for a, b in tr.program_runs(data["trace"], PROGRAM)) * 1e-9
    if not calls or t <= 0:
        return None
    flops = sum(counts.lm_prefill_flops(data["config"], call["M"])
                for call in calls)
    return 100.0 * flops / (t * data["peak"]["flops_per_s"])
