"""Profiler trace -> a small normalised record -> device-time reductions.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps only what the metric readers use, on the trace's own clock (ns):

    {"window": [t0, t1],                    # the harness's window span
     "devices": {"0": {"ops": [[name, start, dur], ...],
                       "modules": [[name, start, dur], ...]}},
     "spans": [[name, start, dur], ...]}    # the harness's host spans

``ops`` are the events of a device's "XLA Ops" line, named by their HLO
instruction and output type (``fusion.37 f32[128,64,64,64]``; a Pallas
kernel is a custom call named after the jitted function that launched
it, e.g. ``zebra_spmm_cs.11 f32[2048,6144]``); ``modules`` those of its
"XLA Modules" line (one per jitted program execution). Host spans are
the ``jax.profiler.TraceAnnotation`` regions the harness opens; their
names start with ``SPAN_PREFIX``.

Everything below ``extract`` works on that record alone, so the CPU
tests check it on a small recorded file.
"""
from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"


def _device_id(plane_name: str) -> str | None:
    m = re.match(r"^/device:TPU:(\d+)$", plane_name)
    return m.group(1) if m else None


_HLO = re.compile(r"^%?([\w.\-]+) = (\S+)")
_LAYOUT = re.compile(r"\{[^}]*\}")
_SUFFIX = re.compile(r"\.\d+(?= |$)")


def op_name(text: str) -> str:
    """An op event's HLO text -> ``<instruction> <output type>`` (layouts
    dropped; tuple outputs named by the instruction alone)."""
    m = _HLO.match(text)
    if not m:
        return text
    typ = m.group(2)
    return m.group(1) if typ.startswith("(") else \
        f"{m.group(1)} {_LAYOUT.sub('', typ).rstrip(',')}"


def save(rec: dict, path) -> None:
    """Write a record as gzipped JSON (what the tests read back)."""
    import gzip
    import json
    with gzip.open(path, "wt") as f:
        json.dump(rec, f)


def load(path) -> dict:
    import gzip
    import json
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    devices: dict[str, dict] = {}
    spans = []
    for plane in pd.planes:
        dev = _device_id(plane.name)
        if dev is not None:
            rec = devices.setdefault(dev, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    name = op_name(ev.name) if key == "ops" else ev.name
                    rec[key].append([name, float(ev.start_ns),
                                     float(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)])
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no window span")
    w = max(win, key=lambda s: s[2])
    return {"window": [w[1], w[1] + w[2]], "devices": devices,
            "spans": [s for s in spans if s[0] != WINDOW_SPAN]}


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def _clip(events, t0: float, t1: float):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _events(rec: dict, dev: str, key: str) -> list:
    return rec["devices"].get(dev, {}).get(key, [])


def busy_intervals(rec: dict, dev: str) -> list[tuple[float, float]]:
    t0, t1 = rec["window"]
    return union((a, b) for _, a, b in _clip(_events(rec, dev, "ops"),
                                             t0, t1))


def busy_seconds(rec: dict) -> float:
    """Seconds in the window in which some op ran, averaged over the
    devices in the record."""
    devs = sorted(rec["devices"])
    if not devs:
        return 0.0
    tot = sum(sum(b - a for a, b in busy_intervals(rec, d)) for d in devs)
    return tot / len(devs) * 1e-9


def window_seconds(rec: dict) -> float:
    t0, t1 = rec["window"]
    return (t1 - t0) * 1e-9


def idle_gaps(rec: dict, dev: str = "0") -> list[tuple[float, float]]:
    """Device-idle intervals inside the window."""
    t0, t1 = rec["window"]
    gaps, prev = [], t0
    for a, b in busy_intervals(rec, dev):
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if t1 > prev:
        gaps.append((prev, t1))
    return gaps


def idle_by_span(rec: dict, dev: str = "0") -> dict[str, float]:
    """Idle seconds by what the host was doing: each gap goes to the
    innermost harness span that covers its midpoint ("no span" else)."""
    spans = [(name[len(SPAN_PREFIX):], s, s + d) for name, s, d in
             rec["spans"]]
    out: dict[str, float] = {}
    for a, b in idle_gaps(rec, dev):
        mid = 0.5 * (a + b)
        cover = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        name = min(cover)[1] if cover else "no span"
        out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def base_name(name: str) -> str:
    """``fusion.123 f32[8]`` -> ``fusion f32[8]``: the instruction's
    numeric suffix dropped, so one kind of op sums across programs."""
    return _SUFFIX.sub("", name)


# control-flow ops whose events span the ops of their bodies
CONTAINERS = ("while", "conditional", "call")


def op_seconds(rec: dict, dev: str = "0", *, modules: bool = False
               ) -> dict[str, float]:
    """Device seconds in the window by op (or program) base name; a loop
    or branch is left out, since its body's ops are counted."""
    t0, t1 = rec["window"]
    key = "modules" if modules else "ops"
    out: dict[str, float] = {}
    for name, a, b in _clip(_events(rec, dev, key), t0, t1):
        n = base_name(name)
        if not modules and n.split(" ")[0] in CONTAINERS:
            continue
        out[n] = out.get(n, 0.0) + (b - a) * 1e-9
    return out


def kernel_seconds(rec: dict, names, dev: str = "0") -> float:
    """Summed device seconds of the ops whose instruction, less its
    numeric suffix, is one of ``names``."""
    names = set(names)
    t0, t1 = rec["window"]
    return sum((b - a) * 1e-9 for n, a, b in
               _clip(_events(rec, dev, "ops"), t0, t1)
               if base_name(n).split(" ")[0] in names)


def program_runs(rec: dict, prefix: str, dev: str = "0"
                 ) -> list[tuple[float, float]]:
    """``(start, end)`` of every execution of the programs whose module
    name starts with ``prefix``, in the window, in time order."""
    t0, t1 = rec["window"]
    return sorted((a, b) for n, a, b in
                  _clip(_events(rec, dev, "modules"), t0, t1)
                  if n.startswith(prefix))


def idle_between(rec: dict, runs, dev: str = "0") -> list[float]:
    """Device-idle seconds between each pair of consecutive ``runs``."""
    busy = busy_intervals(rec, dev)
    out = []
    for (_, e0), (s1, _) in zip(runs, runs[1:]):
        if s1 <= e0:
            out.append(0.0)
            continue
        covered = sum(max(0.0, min(b, s1) - max(a, e0)) for a, b in busy)
        out.append((s1 - e0 - covered) * 1e-9)
    return out


def top(d: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
