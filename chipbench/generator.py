"""The one traffic generator. A traffic mix is a JSON file of parameters
under ``chipbench/traffic/``; this module turns it and a seed into work.

Lengths are drawn by stratified quantiles: a round of ``n`` requests
takes the values of the length distribution at ``(i + 0.5) / n`` for
``i < n``. Prompt and output lengths are paired once, by a permutation
drawn from a constant key (``round_sizes``); the seed only permutes the
whole pairs and draws the token ids. Every seed therefore sends the same
(prompt, output) pairs in another order, so runs with different seeds
do the same amount of work.

Length specs (all inclusive integer bounds):

``{"kind": "fixed", "value": v}``
``{"kind": "uniform_int", "lo": a, "hi": b}``
``{"kind": "choice", "values": [...], "probs": [...]}``
``{"kind": "lognormal_int", "median": m, "sigma": s, "lo": a, "hi": b}``

A prompt is the concatenation of its ``parts`` (e.g. a document chunk
then a question), each drawn from its own spec.
"""
from __future__ import annotations

import math
import statistics

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator keyed by the run's seed and a stream index; any whole
    number works as a seed (it is reduced modulo 2**64)."""
    words = [int(seed) % 2 ** 64] + [int(s) % 2 ** 64 for s in stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def _quantile(spec: dict, u: float) -> int:
    kind = spec["kind"]
    if kind == "fixed":
        return int(spec["value"])
    if kind == "uniform_int":
        lo, hi = int(spec["lo"]), int(spec["hi"])
        return min(lo + int(math.floor(u * (hi - lo + 1))), hi)
    if kind == "choice":
        acc = 0.0
        for v, p in zip(spec["values"], spec["probs"]):
            acc += p
            if u < acc:
                return int(v)
        return int(spec["values"][-1])
    if kind == "lognormal_int":
        z = statistics.NormalDist().inv_cdf(u)
        v = round(math.exp(math.log(spec["median"]) + spec["sigma"] * z))
        return int(min(max(v, spec["lo"]), spec["hi"]))
    raise ValueError(f"unknown length kind {kind!r}")


def stratified(spec: dict, n: int) -> list[int]:
    """The ``n`` stratified values of a length spec, in quantile order."""
    return [_quantile(spec, (i + 0.5) / n) for i in range(n)]


def closed_round(traffic: dict, *, vocab: int, seed: int,
                 round_index: int) -> list[tuple[np.ndarray, int]]:
    """One closed round: ``[(prompt int32 ids, max_new), ...]``, all due
    at the round's start: the pairs of ``round_sizes`` in an order drawn
    from the seed, with token ids drawn from it. ``round_index`` < 0 is
    warm-up traffic: the same pairs, other ids and order."""
    pairs = round_sizes(traffic)
    rng = rng_for(seed, 1, round_index)
    reqs = []
    for i in rng.permutation(len(pairs)):
        plen, max_new = pairs[int(i)]
        prompt = rng.integers(0, vocab, size=plen, dtype=np.int64)
        reqs.append((prompt.astype(np.int32), max_new))
    return reqs


def round_sizes(traffic: dict) -> list[tuple[int, int]]:
    """The (prompt length, output length) pairs of every round, whatever
    the seed: each length spec's stratified values, paired by
    permutations drawn from a constant key."""
    n = int(traffic["round_requests"])
    rng = rng_for(0, 1, 0)
    parts = [np.asarray(stratified(p, n))[rng.permutation(n)]
             for p in traffic["prompt"]["parts"]]
    outs = np.asarray(stratified(traffic["output"], n))[rng.permutation(n)]
    return [(int(sum(int(p[i]) for p in parts)), int(outs[i]))
            for i in range(n)]


def staged_sample(n_done: int, seed: int, k: int,
                  longest: int | None = None) -> list[int]:
    """``k`` indices out of ``range(n_done)`` drawn from the seed, with
    ``longest`` (if given) always among them."""
    rng = rng_for(seed, 2)
    order = [int(i) for i in rng.permutation(n_done)]
    pick = [] if longest is None else [longest]
    for i in order:
        if len(pick) >= k:
            break
        if i not in pick:
            pick.append(i)
    return sorted(pick)
