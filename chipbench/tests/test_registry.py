"""The harness finds every cell, configuration, traffic mix, limit file and
per-layer reader by name, and ``BENCHMARK.json`` keeps to its format."""
import json
import pathlib
import re

import pytest

from chipbench import bench

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    return bench.load_benchmark()


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "chipbench/run.py"]
    assert spec["paths"] == ["chipbench"]
    assert 1 <= spec["run_seconds"] <= 51
    assert len(json.dumps(spec)) < 64 * 1024


def test_configs(spec):
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert (ROOT / "chipbench" / "families" / f"{cfg['family']}.py").exists()


def test_cells_resolve(spec):
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        wl, cfg, traffic, limits, m_e2e, m_layer = bench.resolve(spec, w["name"])
        assert traffic["name"] == w["traffic"]
        assert limits, f"{w['name']} has no limits file"
        assert all(v is not None for v in limits.values())
        names = {m["name"] for m in m_e2e}
        assert "setup_s" in names and len(names) >= 2 and m_layer


def test_metrics(spec):
    cells = {w["name"] for w in spec["workloads"]}
    seen = set()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in seen
        seen.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert callable(bench.reader(m["name"]))
        moved = [e for e in spec["end_to_end"] if e["name"] == m["moves"]]
        assert moved and set(m["workloads"]) <= set(
            moved[0].get("workloads", cells))
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_a_cell_and_a_metric_from_files_alone(tmp_path):
    """A later PR adds a cell and a metric by adding files and entries;
    the harness finds both without an edit."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "limits").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "dummy-model.json").write_text(json.dumps(
        {"name": "dummy-model", "family": "lm_serve"}))
    (tmp_path / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"name": "dummy-mix", "kind": "closed_rounds"}))
    (tmp_path / "limits" / "dummy-cell.json").write_text(
        json.dumps({"max_logit_gap": 1.0}))
    (tmp_path / "metrics" / "dummy_ms.py").write_text(
        "def read(data):\n    return data['counters'].get('x')\n")
    spec = bench.load_benchmark()
    spec["configs"].append({"name": "dummy-model", "source": "test",
                            "file": "configs/dummy-model.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "dummy-cell", "config": "dummy-model",
                              "traffic": "dummy-mix", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][0].setdefault("workloads", []).append("dummy-cell")
    spec["per_layer"].append({"name": "dummy_ms", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "test", "moves": spec["end_to_end"][0]["name"],
                              "workloads": ["dummy-cell"]})
    assert "dummy-cell" in bench.list_cells(spec)
    dirs = (tmp_path, bench.HERE)
    wl, cfg, traffic, limits, e2e, layer = bench.resolve(
        spec, "dummy-cell", base=tmp_path, dirs=dirs)
    assert cfg["family"] == "lm_serve" and traffic["name"] == "dummy-mix"
    assert limits == {"max_logit_gap": 1.0}
    assert [m["name"] for m in layer] == ["dummy_ms"]
    assert bench.reader("dummy_ms", dirs)({"counters": {"x": 3.0}}) == 3.0
    assert bench.reader("dummy_ms", dirs)({"counters": {}}) is None
    # the cells already declared resolve exactly as before
    assert bench.resolve(spec, "r18-infer")[2]["name"] == "images128"


def test_unknown_cell_raises(spec):
    with pytest.raises(KeyError):
        bench.resolve(spec, "no-such-cell")


def test_a_number_without_a_limit_is_not_correct():
    from chipbench.families.lm_serve import _checks, passes
    numbers = {"max_logit_gap": 0.0, "kv_err": 0.0}
    assert _checks(numbers, {}) == {k: {"value": 0.0, "limit": None}
                                    for k in numbers}
    assert not passes(_checks(numbers, {}))
    assert passes(_checks(numbers, {"max_logit_gap": 0.1}))
    assert not passes(_checks({"max_logit_gap": 0.2}, {"max_logit_gap": 0.1}))
