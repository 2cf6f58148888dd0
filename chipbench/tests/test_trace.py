"""Trace reductions on small records: a hand-made one with known answers,
and one recorded on a TPU v5e (a traced serving round of StarCoder2-15B,
8 layers, under an earlier document-Q&A mix, cut to one prefill and the
five decode steps after it)."""
import pathlib

import pytest

from chipbench.metrics.lib import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1e6        # ns per ms

# window 0..10 ms; device busy 1-2, 1.5-3 (overlap), 5-6, 9-12 (clipped)
HAND = {
    "window": [0.0, 10 * MS],
    "devices": {"0": {
        "ops": [["fusion.1 f32[8]", 1 * MS, 1 * MS],
                ["zebra_spmm_cs.11 f32[16,8]", 1.5 * MS, 1.5 * MS],
                ["fusion.22 f32[8]", 5 * MS, 1 * MS],
                ["zebra_mask_pack.3 s32[2,1,1,1]", 9 * MS, 3 * MS]],
        "modules": [["jit_decode_slotted", 1 * MS, 2 * MS],
                    ["jit_prefill", 4.5 * MS, 2 * MS],
                    ["jit_decode_slotted", 8.5 * MS, 1.5 * MS]]}},
    "spans": [["chipbench.schedule", 3 * MS, 2 * MS],
              ["chipbench.pool.page_out", 3.5 * MS, 1 * MS]],
}


def test_busy_union_and_idle():
    assert tr.busy_intervals(HAND, "0") == [(1 * MS, 3 * MS), (5 * MS, 6 * MS),
                                            (9 * MS, 10 * MS)]
    assert tr.busy_seconds(HAND) == pytest.approx(4e-3)
    assert tr.window_seconds(HAND) == pytest.approx(10e-3)
    assert tr.idle_gaps(HAND) == [(0.0, 1 * MS), (3 * MS, 5 * MS),
                                  (6 * MS, 9 * MS)]


def test_idle_attribution_to_innermost_span():
    got = tr.idle_by_span(HAND)
    # gap 3-5 has its midpoint (4) inside page_out (3.5-4.5), the
    # innermost span; the other gaps lie under no span
    assert got == pytest.approx({"pool.page_out": 2e-3, "no span": 4e-3})


def test_per_op_and_per_kernel_seconds():
    ops = tr.op_seconds(HAND)
    assert ops["fusion f32[8]"] == pytest.approx(2e-3)
    assert ops["zebra_mask_pack s32[2,1,1,1]"] == pytest.approx(1e-3)  # clipped
    assert tr.kernel_seconds(HAND, ["zebra_spmm_cs"]) == pytest.approx(1.5e-3)
    assert tr.kernel_seconds(HAND, ["zebra_mask_pack"]) == pytest.approx(1e-3)
    assert tr.top(ops, 1) == [["fusion f32[8]", pytest.approx(2e-3)]]
    loop = {**HAND, "devices": {"0": {"ops": HAND["devices"]["0"]["ops"] + [
        ["while.2", 0.5 * MS, 3 * MS]], "modules": []}}}
    assert "while" not in tr.op_seconds(loop)        # its body is counted


def test_program_runs_and_gaps_between():
    runs = tr.program_runs(HAND, "jit_decode_slotted")
    assert runs == [(1 * MS, 3 * MS), (8.5 * MS, 10 * MS)]
    # 3 -> 8.5 ms between the two decode programs, 1 ms of it busy (5-6)
    assert tr.idle_between(HAND, runs) == [pytest.approx(4.5e-3)]


def test_missing_device_reads_nothing():
    rec = {"window": [0.0, 1.0], "devices": {}, "spans": []}
    assert tr.busy_seconds(rec) == 0.0
    assert tr.op_seconds(rec) == {} and tr.program_runs(rec, "jit") == []


def test_op_names_from_hlo_text():
    hlo = ("%zebra_spmm_cs.11 = f32[2048,6144]{1,0:T(8,128)S(1)} "
           "custom-call(s32[49152]{0:T(1024)} %a)")
    assert tr.op_name(hlo) == "zebra_spmm_cs.11 f32[2048,6144]"
    assert tr.op_name("%while.2 = (s32[]{:T(128)}, bf16[8]) while(%t)") == "while.2"
    assert tr.base_name("fusion.123 f32[8]") == "fusion f32[8]"
    assert tr.base_name("copy_start.4") == "copy_start"
    assert tr.base_name("zebra_mask_pack") == "zebra_mask_pack"


def test_recorded_chip_trace():
    rec = tr.load(DATA / "trace_sc2_docqa_cut.json.gz")
    assert set(rec) == {"window", "devices", "spans"}
    busy, win = tr.busy_seconds(rec), tr.window_seconds(rec)
    assert 0 < busy < win
    runs = tr.program_runs(rec, "jit_decode_slotted")
    assert len(runs) >= 3
    assert all(g >= 0 for g in tr.idle_between(rec, runs))
    # one prefill of 512 tokens: the FFN producer and consumer kernels
    assert 0 < tr.kernel_seconds(rec, ["zebra_mask_pack"]) \
        < tr.kernel_seconds(rec, ["zebra_spmm_cs"])
    idle = tr.idle_by_span(rec)
    assert sum(idle.values()) == pytest.approx(win - busy, rel=1e-9)
