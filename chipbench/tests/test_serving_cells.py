"""The serving family (``families/lm_serve.py``) against a traced serving
round of StarCoder2-15B (8 layers) recorded on a TPU v5e: each serving
per-layer reader reads a number from it, with counters the harness's
own ``round_counters`` builds for that round, of the kind a traced
rehearsal hands the readers; and the rule that names a serving cell's
TTFT percentile."""
import json
import math
import pathlib
import pytest

from chipbench import bench, peaks
from chipbench.families import lm_serve
from chipbench.metrics.lib import trace as tr
from chipbench.tests import tiny

DATA = pathlib.Path(__file__).resolve().parent / "data"
RECORDED = DATA / "trace_sc2_docqa_cut.json.gz"
CONFIG = bench.HERE / "configs" / "starcoder2-15b-d8.json"
SERVING_READERS = ("mfu.serve", "idle_share.serve", "decode_step_ms",
                   "decode_gap_ms", "pool_ms_per_admit", "mfu.prefill",
                   "mask_pack_roofline", "spmm_cs_roofline")


def _recorded_round(config):
    """Counters for the recorded round: one prefill of 512 tokens (the
    trace's one ``zebra_mask_pack`` call) at a quarter of its FFN blocks
    zero, the decode steps after it, the pool's time from the trace's
    pool spans, one admission."""
    rec = tr.load(RECORDED)
    pool_s = sum(d for n, _, d in rec["spans"]
                 if n.startswith(tr.SPAN_PREFIX + "pool.")) * 1e-9
    L, z = config["num_hidden_layers"], config["served"]["zebra"]
    n_blocks = L * (512 // z["block_seq"]) * (config["intermediate_size"]
                                              // z["block_ch"])
    counters = lm_serve.round_counters(
        config, [(512, 0.25 * n_blocks)], range(512, 518),
        tr.window_seconds(rec), pool_s, 1)
    return rec, counters


def test_round_counters_are_the_rehearsals(monkeypatch, tmp_path):
    """The counters built for the recorded round have the keys a traced
    run of the tiny serving cell hands its readers."""
    seen = []
    real = lm_serve._layer_data

    def keep(*a, **k):
        out = real(*a, **k)
        seen.append(out[0])
        return out
    monkeypatch.setattr(lm_serve, "_layer_data", keep)
    res = tiny.run("tiny-chat", 21, trace=True, out_dir=tmp_path)
    assert res["correct"] and len(seen) == 1
    config = json.loads(CONFIG.read_text())
    assert set(_recorded_round(config)[1]) == set(seen[0])


@pytest.mark.parametrize("name", SERVING_READERS)
def test_serving_reader_reads_the_recorded_round(name):
    config = json.loads(CONFIG.read_text())
    rec, counters = _recorded_round(config)
    data = {"trace": rec, "counters": counters, "config": config,
            "traffic": {}, "peak": peaks.peaks("TPU v5 lite")}
    v = bench.reader(name)(data)
    assert v is not None and math.isfinite(v) and v > 0
    if "roofline" in name or "share" in name or "mfu" in name:
        assert v <= 100


def test_serving_readers_read_nothing_without_their_inputs():
    """A reader with nothing to read returns nothing, never a 0 share."""
    rec = {"window": [0.0, 1e9], "devices": {"0": {"ops": [],
                                                   "modules": []}},
           "spans": []}
    data = {"trace": rec, "counters": {}, "config": json.loads(
        CONFIG.read_text()), "traffic": {}, "peak": peaks.peaks(
            "TPU v5 lite")}
    for name in SERVING_READERS:
        if name != "idle_share.serve":
            assert bench.reader(name)(data) is None, name


def ttft_rule(n: int) -> int:
    """The percentile a serving cell's TTFT metric takes when its
    smallest window holds ``n`` requests: the highest of 50, 80, 90 and
    95 that leaves ten samples beyond it, and the median below forty."""
    if n < 40:
        return 50
    return max(q for q in (50, 80, 90, 95) if n * (100 - q) / 100 >= 10)


def test_ttft_rule_and_what_the_family_reports():
    assert [ttft_rule(n) for n in (32, 40, 50, 64, 99, 100, 199, 200)] == [
        50, 50, 80, 80, 80, 90, 90, 95]
    assert {ttft_rule(n) for n in range(1, 2000)} == set(
        lm_serve.TTFT_PERCENTILES)
