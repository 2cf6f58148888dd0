"""Mean device-idle time between consecutive slotted-decode programs:
the host's time per engine tick that the device waits through."""
from chipbench.metrics.lib import trace as tr

PROGRAM = "jit_decode_slotted"


def read(data):
    gaps = tr.idle_between(data["trace"], tr.program_runs(data["trace"], PROGRAM))
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e3
