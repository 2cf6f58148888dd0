"""The harness: one run of one cell, driven by ``BENCHMARK.json``.

Everything specific to a configuration, a traffic mix, a cell's limits
or a per-layer metric lives in a file of its own, found by name:

    chipbench/configs/<config>.json      sizes as run, source, deployment
    chipbench/traffic/<traffic>.json     parameters of the one generator
    chipbench/limits/<workload>.json     the limits of the cell's checks
    chipbench/metrics/<metric>.py        a reader: ``read(data) -> float | None``
    chipbench/families/<family>.py       how a family of configurations runs

A configuration names its ``family``; the family's module builds the
system under test, warms it up, runs the timed window, checks the output
against the plain reference (``chipbench/reference``) and returns the
numbers. This module turns them into the result line.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def load_benchmark(path: pathlib.Path | None = None) -> dict:
    with open(path or ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """Everything one run of one cell needs, resolved from the files."""
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    out_dir: pathlib.Path

    @property
    def name(self) -> str:
        return self.workload["name"]


def _find(sub: str, name: str, dirs) -> pathlib.Path | None:
    for d in dirs:
        p = pathlib.Path(d) / sub / name
        if p.exists():
            return p
    return None


def resolve(bench: dict, workload: str, *, base: pathlib.Path = ROOT,
            dirs=(HERE,)):
    """The cell's workload, configuration, traffic, limits and metrics;
    data files are looked up in ``dirs`` in order."""
    wl = [w for w in bench["workloads"] if w["name"] == workload]
    if len(wl) != 1:
        raise KeyError(f"workload {workload!r} is not in BENCHMARK.json")
    wl = wl[0]
    cfgs = [c for c in bench["configs"] if c["name"] == wl["config"]]
    if len(cfgs) != 1:
        raise KeyError(f"configuration {wl['config']!r} is not declared")
    config = _json(base / cfgs[0]["file"])
    tpath = _find("traffic", f"{wl['traffic']}.json", dirs)
    if tpath is None:
        raise KeyError(f"traffic {wl['traffic']!r} has no file")
    traffic = _json(tpath)
    lim = _find("limits", f"{workload}.json", dirs)
    limits = _json(lim) if lim else {}
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return wl, config, traffic, limits, e2e, layer


def list_cells(bench: dict) -> list[str]:
    return [w["name"] for w in bench["workloads"]]


def reader(name: str, dirs=(HERE,)):
    """The per-layer metric's reader, loaded from ``metrics/<name>.py``."""
    path = _find("metrics", f"{name}.py", dirs)
    if path is None:
        raise KeyError(f"per-layer metric {name!r} has no reader")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def family(name: str):
    return importlib.import_module(f"chipbench.families.{name}")


def setup_program_path() -> None:
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def use_compile_cache() -> str:
    """The program's persistent compilation cache (its fixed directory in
    the checkout, or ``JAX_COMPILATION_CACHE_DIR``), holding every
    program however quickly it compiled, so a cell's second run in a
    checkout compiles nothing."""
    import jax
    from repro.launch.cache import use_compile_cache as program_cache
    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts, while ``active``, the programs JAX had to build: requests
    to the compilation cache (a load or a compile) and the compiles
    among them. A timed window should count none of either."""

    def __init__(self):
        import jax.monitoring as mon
        self.requests = self.compiles = 0
        self.active = False
        self.load_s = self.compile_s = 0.0      # whole run, set-up included
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_) -> None:
        if self.active and event.endswith("compile_requests_use_cache"):
            self.requests += 1

    def _duration(self, event: str, duration: float, **_) -> None:
        if event.endswith("cache_retrieval_time_sec"):
            self.load_s += duration
        if event.endswith("backend_compile_duration"):
            self.compile_s += duration
            self.compiles += self.active


def memory_peak_bytes(n_used: int) -> int:
    """Peak device memory on the fullest chip so far (0 where the backend
    does not report it). Families read it after the window and before
    the reference runs."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:n_used])


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench: dict | None = None,
             require_chip: bool = True,
             out_dir: pathlib.Path | None = None,
             config_dir: pathlib.Path = ROOT, dirs=(HERE,)) -> dict | None:
    """One run; prints the result line and returns it (None when refused).

    ``require_chip=False`` is the CPU rehearsal: the run goes through the
    same family module, reports no metric, and names the CPU it ran on."""
    bench = bench or load_benchmark()
    wl, config, traffic, limits, e2e, layer = resolve(
        bench, workload, base=config_dir, dirs=dirs)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no program under {ROOT / 'src'}", file=sys.stderr)
        return None
    setup_program_path()
    import jax
    devs = jax.devices()
    chips = int(wl["chips"])
    on_tpu = devs[0].platform == "tpu"
    if require_chip and (not on_tpu or len(devs) < chips):
        print(f"chipbench: {workload} needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        return None
    if on_tpu:
        use_compile_cache()
    out_dir = out_dir or ROOT / ".chipbench"
    cell = Cell(workload=wl, config=config, traffic=traffic, limits=limits,
                seed=int(seed),
                seconds=float(seconds), trace=bool(trace), t_start=t_start,
                out_dir=pathlib.Path(out_dir))
    res = family(config["family"]).run(cell)

    metrics: dict = {}
    if on_tpu and not trace:
        for m in e2e:
            metrics[m["name"]] = {"value": res["e2e"][m["name"]],
                                  "unit": m["unit"]}
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": chips, "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": bool(res["correct"]), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics, "device": dev}
    if trace and res.get("trace") is not None:
        from chipbench import peaks
        from chipbench.metrics.lib import trace as tr
        data = {"trace": res["trace"], "counters": res.get("counters", {}),
                "config": config, "traffic": traffic,
                "peak": peaks.peaks(devs[0].device_kind) if on_tpu else None}
        if on_tpu:
            for m in layer:
                v = reader(m["name"], dirs)(data)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
            dev["busy_s"] = tr.busy_seconds(res["trace"])
            dev["window_s"] = tr.window_seconds(res["trace"])
        out["breakdown"] = res.get("breakdown", {})
    out["checks"] = res["checks"]
    for k, v in res.get("notes", {}).items():
        print(f"chipbench: {k} = {v}", file=sys.stderr)
    for name, c in res["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return out
