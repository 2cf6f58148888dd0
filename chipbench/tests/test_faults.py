"""A run with its timed path broken underneath comes out not correct:
the harness's look for a chip is skipped, the rest of the run is the real
one at toy sizes. Faults a cell can have: a decode step that returns its
state unchanged, a token or an answer altered where it is produced, half
of a batch left out, and for a served LM the pool's page-in handing back
zeros (the check takes the all-zero cache as the K/V gate's decisions,
so what fails is those decisions: every block, far above the threshold,
dropped), and a served token one place below the best where the best
leads by more than a non-zero ``max_logit_gap`` limit. (No cell spans chips,
so no exchange can be left out.)"""
import json
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import bench
from chipbench.families import cnn_infer
from chipbench.tests import tiny


def _broken_decode(monkeypatch, fault):
    import repro.serve.engine as engine
    real = engine.make_decode_slotted

    def make(model, mesh, temperature=0.0):
        step = real(model, mesh, temperature)

        def broken(params, token, state, pos, key):
            nxt, new_state = step(params, token, state, pos, key)
            if fault == "state":
                return nxt, state
            return (nxt + 1) % model.cfg.vocab, new_state
        return broken
    monkeypatch.setattr(engine, "make_decode_slotted", make)


def _broken_page_in(monkeypatch):
    from repro.serve.pool import PagedKVPool
    real = PagedKVPool.page_in

    def page_in(self, rid):
        return jax.tree_util.tree_map(jnp.zeros_like, real(self, rid))
    monkeypatch.setattr(PagedKVPool, "page_in", page_in)


@pytest.mark.parametrize("fault,number", [("state", "kv_err"),
                                          ("token", "max_logit_gap"),
                                          ("page_in", "flip_share")])
def test_lm_fault_is_not_correct(monkeypatch, tmp_path, fault, number):
    if fault == "page_in":
        _broken_page_in(monkeypatch)
    else:
        _broken_decode(monkeypatch, fault)
    res = tiny.run("tiny-chat", 11, out_dir=tmp_path)
    c = res["checks"][number]
    assert not res["correct"] and c["value"] > c["limit"]


def _planted_decode(monkeypatch, margin):
    """The decode step serves the second-best token of its own logits
    wherever the best leads by more than ``margin``."""
    import repro.serve.engine as engine

    def make(model, mesh, temperature=0.0):
        def planted(params, token, state, pos, key):
            logits, state = model.decode_step(params, token, state, pos)
            top, idx = jax.lax.top_k(logits.astype(jnp.float32), 2)
            nxt = jnp.where(top[:, 0] - top[:, 1] > margin, idx[:, 1],
                            idx[:, 0])
            return nxt.astype(jnp.int32)[:, None], state
        return planted
    monkeypatch.setattr(engine, "make_decode_slotted", make)


@pytest.mark.parametrize("limit", [0.05, 1.0])
def test_lm_token_one_place_down_is_not_correct(monkeypatch, tmp_path, limit):
    """A limit on ``max_logit_gap`` above 0 still fails a token one place
    below the best by more than twice the limit; the prefill logits and
    the cache, which the planted tokens do not touch, still pass."""
    _planted_decode(monkeypatch, 2 * limit)
    (tmp_path / "limits").mkdir()
    (tmp_path / "limits" / "tiny-chat.json").write_text(json.dumps(
        {**tiny.cell("tiny-chat").limits, "max_logit_gap": limit}))
    res = bench.run_cell("tiny-chat", 11, 0.5, False, t_start=time.time(),
                         bench=tiny.spec(), require_chip=False,
                         out_dir=tmp_path, config_dir=tiny.DATA,
                         dirs=(tmp_path,) + tiny.DIRS)
    c = res["checks"]
    assert not res["correct"]
    assert c["max_logit_gap"]["limit"] == limit
    assert c["max_logit_gap"]["value"] > limit
    for k in ("prefill_logit_err", "kv_err"):
        assert c[k]["value"] <= c[k]["limit"]


def _broken_forward(monkeypatch, fault):
    real = cnn_infer.program

    def program(c):
        model, zc, init, fwd = real(c)

        def answer_altered(v, x):
            y = fwd(v, x)
            return y.at[0].set(y[0, ::-1])

        def half_batch(v, x):
            h = x.shape[0] // 2
            y = fwd(v, jnp.concatenate([x[:h], x[:h]]))
            return y.at[h:].set(jnp.mean(y[:h], axis=0))
        broken = {"answer": answer_altered, "half": half_batch}[fault]
        return model, zc, init, jax.jit(broken)
    monkeypatch.setattr(cnn_infer, "program", program)


@pytest.mark.parametrize("fault", ["answer", "half"])
def test_cnn_fault_is_not_correct(monkeypatch, tmp_path, fault):
    _broken_forward(monkeypatch, fault)
    res = tiny.run("tiny-images", 13, out_dir=tmp_path)
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
