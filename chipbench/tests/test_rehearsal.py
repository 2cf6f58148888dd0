"""The whole run of each cell family on the CPU at toy sizes, through the
harness's own path (no device metric is reported off a TPU), and the
refusals of the command itself."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

from chipbench.metrics.lib import trace
from chipbench.tests import tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_lm_cell_on_cpu(tmp_path):
    res = tiny.run("tiny-chat", 2 ** 31 + 99, out_dir=tmp_path)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 6
    assert res["metrics"] == {}
    assert res["device"]["platform"] == "cpu"
    assert list(res["checks"]) == ["prefill_logit_err", "kv_err",
                                   "max_logit_gap", "flip_share",
                                   "flip_margin"]
    assert list(res)[-1] == "checks"


def test_lm_cell_traced_on_cpu(tmp_path):
    """A traced run serves its whole round but profiles only its first
    ``trace_ticks`` engine ticks: the trace holds that many decode
    steps, and the round goes on past them."""
    res = tiny.run("tiny-chat", 5, trace=True, out_dir=tmp_path)
    assert res["correct"] and res["metrics"] == {}
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    (path,) = tmp_path.glob("trace_tiny-chat_5.json.gz")
    rec = trace.load(path)
    steps = [s for s in rec["spans"]
             if s[0] == trace.SPAN_PREFIX + "decode_step"]
    traffic = tiny.cell("tiny-chat").traffic
    ticks = traffic["trace_ticks"]
    assert traffic["output"]["lo"] > ticks   # each request outlasts the slice
    assert len(steps) == ticks
    assert all(rec["window"][0] <= s[1] and s[1] + s[2] <= rec["window"][1]
               for s in steps)
    assert any(s[0] == trace.SPAN_PREFIX + "prefill" for s in rec["spans"])


def test_cnn_cell_on_cpu(tmp_path):
    res = tiny.run("tiny-images", 3, out_dir=tmp_path)
    assert res["correct"] and res["attempted"] >= 1 and res["metrics"] == {}
    assert set(res["checks"]) == {"top1_logit_gap", "logit_err_rms"}


def _cmd(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_command_refuses_without_a_tpu():
    p = _cmd(ROOT, "--workload", "r18-infer", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cmd(tmp_path, "--workload", "r18-infer", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert json.loads((tmp_path / "BENCHMARK.json").read_text())["paths"] == ["chipbench"]
