"""The serving check's decision-matched pass at toy sizes on the CPU.

The reference takes, at every Zebra site, layer and block, the decision
the output under test took: what the K/V cache shows, and at the FFN
site what the next layer's cache rows (or, at the last layer, the
prefill's last-row logits) show. These tests hold it to that: a known
output's decisions are recovered exactly, and a gate that decides by
another threshold fails on ``flip_margin`` though its arithmetic
passes."""
import dataclasses

import numpy as np

from chipbench import control
from chipbench.families import lm_serve
from chipbench.reference import starcoder2 as ref
from chipbench.tests import tiny


def _checked(monkeypatch, seed):
    """The checked requests of a served tiny round, as the window keeps
    them."""
    cell = tiny.cell("tiny-chat")
    row = {}

    def keep(c, key, got, rows):
        row["got"], row["key"], row["rows"] = got, key, rows
        return {k: 0.0 for k in lm_serve.NUMBERS}, 1.0
    with monkeypatch.context() as m:
        m.setattr(lm_serve, "readings", keep)
        control.lm_seed(cell, seed, check_weights=False, with_control=False)
    return cell, row["key"], row["got"], row["rows"]


def test_recovered_decisions_are_the_ones_taken(monkeypatch):
    """The reference's own pass at bfloat16 products, put in the
    program's place, takes some FFN decisions other than float32's; the
    decision-matched pass recovers every one of them that shows in the
    output, and no flip it counts lies far from the threshold."""
    cell, key, got, (kmax, lbmax) = _checked(monkeypatch, 31)
    c = cell.config
    out, taken = control.lm_control(c, key, got, "bf16", (kmax, lbmax))
    seqs = [r.seq for r in out]

    def passes(**k):
        return ref.forward(c, c["served"]["zebra"], key, seqs, kmax=kmax,
                           lbmax=lbmax, **k)
    own = {i: r["keep"] for i, r in passes()}
    shown = differ = 0
    for i, res in passes(outputs=[r.output for r in out]):
        for li, ((kA, kB), (eA, eB)) in enumerate(zip(res["keep"],
                                                      res["ev"])):
            tA, tB = taken[i][li]
            assert np.array_equal(kA[eA], tA[eA])
            assert np.array_equal(kB[eB], tB[eB])
            shown += int(eA.sum() + eB.sum())
            differ += int((tA != own[i][li][0])[eA].sum()
                          + (tB != own[i][li][1])[eB].sum())
    assert shown > 0 and differ > 0


def test_gate_at_another_threshold_fails_on_flip_margin(monkeypatch,
                                                        tmp_path):
    """A served gate at 1.05 x T_obj zeroes blocks the stated gate keeps:
    the arithmetic under its decisions still passes, ``flip_margin``
    does not."""
    real = lm_serve.lm_config

    def raised(c):
        cfg = real(c)
        return dataclasses.replace(cfg, zebra_t_obj=1.05 * cfg.zebra_t_obj)
    monkeypatch.setattr(lm_serve, "lm_config", raised)
    res = tiny.run("tiny-chat", 41, out_dir=tmp_path)
    c = res["checks"]
    assert not res["correct"]
    assert c["flip_margin"]["value"] > c["flip_margin"]["limit"]
    for k in ("prefill_logit_err", "kv_err"):
        assert c[k]["value"] <= c[k]["limit"]
