"""Tiny cells for the CPU rehearsals: the served StarCoder2 block and the
served ResNet-18 layout at toy widths, under small traffic mixes."""
import copy
import pathlib
import time

from chipbench import bench

DATA = pathlib.Path(__file__).resolve().parent / "data"
DIRS = (DATA, bench.HERE)
CELLS = {"tiny-chat": "tiny-lm", "tiny-images": "tiny-cnn"}


def spec() -> dict:
    s = copy.deepcopy(bench.load_benchmark())
    s["configs"] = [{"name": c, "source": "test", "file": f"configs/{c}.json",
                     "reduced": [], "why": "test"} for c in CELLS.values()]
    s["workloads"] = [{"name": w, "config": c, "traffic": w, "chips": 1,
                       "why": "test"} for w, c in CELLS.items()]
    for m in s["end_to_end"]:
        if m["name"] in ("tokens_per_s", "ttft_p95_ms", "itl_p95_ms"):
            m["workloads"] = ["tiny-chat"]
        elif "workloads" in m:
            m["workloads"] = ["tiny-images"]
    s["per_layer"] = []
    return s


def run(workload: str, seed: int, seconds: float = 0.5, trace: bool = False,
        out_dir=None):
    return bench.run_cell(workload, seed, seconds, trace, t_start=time.time(),
                          bench=spec(), require_chip=False, out_dir=out_dir,
                          config_dir=DATA, dirs=DIRS)


def cell(workload: str) -> bench.Cell:
    wl, config, traffic, limits, e2e, layer = bench.resolve(
        spec(), workload, base=DATA, dirs=DIRS)
    bench.setup_program_path()
    return bench.Cell(workload=wl, config=config, traffic=traffic,
                      limits=limits, seed=0,
                      seconds=0.5, trace=False, t_start=time.time(),
                      out_dir=DATA)
