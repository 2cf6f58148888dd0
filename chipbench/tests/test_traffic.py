"""The one traffic generator: seeds, stratified sizes, the cells' mixes."""
import json
import math
import pathlib
import statistics

import numpy as np
import pytest

from chipbench import generator as g

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"

BIG = 2 ** 31 + 12345


def load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["code", "chat"])
def test_same_seed_same_round(name):
    tr = load(name)
    a = g.closed_round(tr, vocab=49152, seed=BIG, round_index=3)
    b = g.closed_round(tr, vocab=49152, seed=BIG, round_index=3)
    assert len(a) == tr["round_requests"]
    for (pa, ma), (pb, mb) in zip(a, b):
        assert ma == mb and np.array_equal(pa, pb)
        assert pa.dtype == np.int32 and 0 <= pa.min() and pa.max() < 49152


@pytest.mark.parametrize("name", ["code", "chat"])
def test_seeds_change_order_not_sizes(name):
    tr = load(name)
    a = g.closed_round(tr, vocab=100, seed=1, round_index=0)
    b = g.closed_round(tr, vocab=100, seed=2 ** 40 + 7, round_index=0)
    c = g.closed_round(tr, vocab=100, seed=1, round_index=1)
    # the same (prompt, output) pairs in another order
    totals = [sum(len(p) for p, _ in r) for r in (a, b, c)]
    outs = [sorted(m for _, m in r) for r in (a, b, c)]
    assert totals[0] == totals[1] == totals[2]
    assert outs[0] == outs[1] == outs[2]
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]


@pytest.mark.parametrize("name", ["code", "chat"])
def test_every_seed_serves_the_same_pairs(name):
    """The (prompt, output) pairing is fixed: every round of every seed
    serves the same pairs, so the same prompt and output tokens."""
    tr = load(name)
    want = sorted(g.round_sizes(tr))
    for seed, k in ((1, 0), (BIG, 0), (2 ** 40 + 7, 3), (5, -1)):
        r = g.closed_round(tr, vocab=100, seed=seed, round_index=k)
        assert sorted((len(p), m) for p, m in r) == want
    assert len(set(want)) > len(want) // 2


def _lognormal_matches(spec, values, median_tol):
    assert spec["lo"] <= min(values) and max(values) <= spec["hi"]
    assert abs(statistics.median(values) - spec["median"]) <= median_tol
    logs = [math.log(x) for x in values if spec["lo"] < x < spec["hi"]]
    assert abs(statistics.pstdev(logs) - spec["sigma"]) < 0.2 * spec["sigma"]


@pytest.mark.parametrize("name,prompt_median,output_median",
                         [("code", 1500, 13), ("chat", 1020, 129)])
def test_lengths_match_the_cited_medians(name, prompt_median, output_median):
    """The medians are the source's (Azure LLM inference trace 2023)."""
    tr = load(name)
    assert "arXiv:2311.18677" in tr["source"]
    n = tr["round_requests"]
    p_spec, o_spec = tr["prompt"]["parts"][0], tr["output"]
    assert (p_spec["median"], o_spec["median"]) == (prompt_median,
                                                    output_median)
    _lognormal_matches(p_spec, g.stratified(p_spec, n), 0.04 * prompt_median)
    _lognormal_matches(o_spec, g.stratified(o_spec, n),
                       max(1, 0.04 * output_median))
    # a request never outgrows the published 4096-token window
    assert p_spec["hi"] + o_spec["hi"] <= 4096


def test_staged_sample_keeps_the_longest():
    pick = g.staged_sample(50, BIG, 5, longest=17)
    assert 17 in pick and len(pick) == 5 == len(set(pick))
    assert pick == g.staged_sample(50, BIG, 5, longest=17)
    assert g.staged_sample(3, 1, 5) == [0, 1, 2]


def test_unknown_length_kind_raises():
    with pytest.raises(ValueError):
        g.stratified({"kind": "zipf"}, 4)
