"""Device time per slotted-decode program (``make_decode_slotted``), from
the trace's program executions."""
from chipbench.metrics.lib import trace as tr

PROGRAM = "jit_decode_slotted"


def read(data):
    runs = tr.program_runs(data["trace"], PROGRAM)
    if not runs:
        return None
    return sum(b - a for a, b in runs) / len(runs) * 1e-6
