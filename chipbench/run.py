"""Run one benchmark cell once on the chip(s) of this machine.

    python3 chipbench/run.py --workload sc2-code --seed 7 --seconds 30 --trace 0

The cell, its configuration, traffic and limits come from
``BENCHMARK.json`` and the files it names. The last line of standard
output is the result as one JSON object; the numbers the correctness
check compared, each beside its limit, are the last lines of standard
error. Without a TPU (or with fewer chips than the cell asks for) the run
prints no result and exits 2.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    res = bench.run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                         t_start=T_START)
    return 2 if res is None else 0


if __name__ == "__main__":
    sys.exit(main())
