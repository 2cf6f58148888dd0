"""Chaos tests: the (ingest boundary x fault class) matrix.

Every cell asserts THREE things: the injected fault was *detected*
(``integrity.failures()`` / a raised ``CorruptStream`` — output parity
alone cannot distinguish "detected and recovered" from "fault never
bit"), the pipeline *recovered* instead of failing, and the recovered
output matches the unfaulted run (bitwise where the backend contract is
bitwise — stream unpack, all_gather — tolerance only for the fused GEMM,
whose recovery recomputes the matmul in a different accumulation order).

Boundaries: engine producer->consumer (in-graph), serve's concrete
prefill->decode handoff (host-side), checkpoint restore (on-disk),
ring collectives (8-device subprocess), step supervisor (policy table).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compress import integrity
from repro.core.engine import zebra_site
from repro.core.zebra import ZebraConfig
from repro.ft import (CorruptStream, DeviceLoss, Fault, FTConfig, PoisonBatch,
                      StepSupervisor, TransientStep, classify, corrupt_file,
                      corrupt_map, crashing_step, inject, policy_for)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Wire contract unit
# ---------------------------------------------------------------------------

def test_validation_level_unknown():
    with pytest.raises(ValueError, match="unknown validation level"):
        ZebraConfig(validation="paranoid")


def _toy_stream(seed=0, nb=8, bs=4, bc=8, n_live=5):
    rng = np.random.default_rng(seed)
    payload = np.zeros((nb, bs, bc), np.float32)
    payload[:n_live] = rng.normal(size=(n_live, bs, bc)) + 2.0  # nonzero
    bitmap = np.zeros((2, 4), np.int8)
    bitmap.reshape(-1)[:n_live] = 1
    return jnp.asarray(payload), jnp.asarray(bitmap), jnp.int32(n_live)


def test_checksum_ignores_dead_tail():
    """Producers that zero the worst-case tail and producers that leave
    garbage there must hash identically — only live slots are signed."""
    payload, bitmap, n_live = _toy_stream()
    garbage = np.array(payload)
    garbage[int(n_live):] = 7.25
    c0 = integrity.stream_checksum(payload, bitmap, n_live)
    c1 = integrity.stream_checksum(jnp.asarray(garbage), bitmap, n_live)
    assert int(c0) == int(c1)
    # ...but a live-slot change must move the fold
    live_edit = np.array(payload)
    live_edit[0, 0, 0] += 1.0
    assert int(integrity.stream_checksum(jnp.asarray(live_edit), bitmap,
                                         n_live)) != int(c0)


def test_validate_payload_names_invariant():
    payload, bitmap, n_live = _toy_stream()
    with pytest.raises(CorruptStream, match="popcount"):
        integrity.validate_payload(payload, bitmap, int(n_live) + 1,
                                   level="structural")
    nanp = np.array(payload)
    nanp[2, 1, 1] = np.nan
    with pytest.raises(CorruptStream, match="non-finite"):
        integrity.validate_payload(nanp, bitmap, n_live, level="structural")
    trunc = np.array(payload)
    trunc[int(n_live) - 1] = 0.0
    with pytest.raises(CorruptStream, match="all-zero"):
        integrity.validate_payload(trunc, bitmap, n_live, level="structural")
    # off level checks nothing
    integrity.validate_payload(nanp, bitmap, n_live, level="off")


# ---------------------------------------------------------------------------
# Failure taxonomy
# ---------------------------------------------------------------------------

def test_classify_and_policies():
    assert policy_for(CorruptStream("x")) == "recompute-dense"
    assert policy_for(TransientStep("x")) == "restore-retry"
    assert policy_for(PoisonBatch("x")) == "skip-batch"
    assert policy_for(DeviceLoss("x")) == "remesh"
    # status-marker matching for errors raised outside the taxonomy
    assert classify(RuntimeError("worker preempted")) is TransientStep
    assert classify(OSError("connection reset by peer")) is TransientStep
    assert classify(FloatingPointError("overflow")) is PoisonBatch
    # unrecognized errors are bugs, not faults
    assert classify(ValueError("bad argument")) is None
    assert classify(KeyError("w")) is None
    assert classify(KeyboardInterrupt()) is None
    assert policy_for(AssertionError()) is None


def test_deterministic_device_errors_are_not_transient():
    """A kernel the compiler refuses, or a program that overflows HBM or
    SMEM, fails the same way on every retry: both must surface as bugs
    (``None``), never be retried with backoff."""
    mosaic = jax.errors.JaxRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: Invalid relayout")
    hbm = jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
        "allocate 2.00G. That was not possible. There are 1.50G free.")
    smem = RuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in memory "
                        "space smem. Used 2.00M of 1.00M smem.")
    for exc in (mosaic, hbm, smem):
        assert classify(exc) is None, exc
        assert policy_for(exc) is None
    # a genuinely transient runtime status still retries
    assert classify(jax.errors.JaxRuntimeError(
        "UNAVAILABLE: TPU worker lost")) is TransientStep


# ---------------------------------------------------------------------------
# Engine boundary (in-graph check + lax.cond recompute-from-dense)
# ---------------------------------------------------------------------------

_ENG = ZebraConfig(t_obj=0.8, block_seq=8, block_ch=128, mode="infer")


def _eng_x():
    return jax.random.normal(jax.random.PRNGKey(0), (2, 32, 256), jnp.float32)


@pytest.mark.parametrize("kind,level", [
    ("bitflip", "structural"), ("truncate", "structural"),
    ("nan", "structural"), ("count", "structural"),
    ("value", "checksum"),
])
def test_engine_stream_detect_recover_bitwise(kind, level):
    x = _eng_x()
    cfg = _ENG.replace(backend="stream", validation=level)
    y_clean, _ = zebra_site(x, cfg, site="m")
    integrity.clear_failures()
    with inject(Fault(kind=kind, site="engine:m", arg=3)) as plan:
        y_f, _ = zebra_site(x, cfg, site="m")
        jax.block_until_ready(y_f)
    assert plan.injected == [(kind, "engine:m")]
    assert integrity.failures() == ["engine:m"], "detection must fire"
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_clean))


@pytest.mark.parametrize("kind,level", [
    ("bitflip", "structural"), ("value", "checksum"),
])
def test_engine_fused_detect_recover(kind, level):
    x = _eng_x()
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 64), jnp.float32)
    cfg = _ENG.replace(backend="fused", validation=level)
    y_clean, _ = zebra_site(x, cfg, site="f", w=w)
    integrity.clear_failures()
    with inject(Fault(kind=kind, site="engine:f")) as plan:
        y_f, _ = zebra_site(x, cfg, site="f", w=w)
        jax.block_until_ready(y_f)
    assert plan.injected == [(kind, "engine:f")]
    assert integrity.failures() == ["engine:f"]
    # fused recovery re-runs the GEMM in reference accumulation order
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_clean),
                               atol=1e-4, rtol=1e-4)


def test_engine_value_invisible_at_structural():
    """The level boundary, stated as a test: a finite nonzero value flip
    passes every structural invariant — only the checksum sees it."""
    x = _eng_x()
    cfg = _ENG.replace(backend="stream", validation="structural")
    integrity.clear_failures()
    with inject(Fault(kind="value", site="engine:m")):
        y_f, _ = zebra_site(x, cfg, site="m")
        jax.block_until_ready(y_f)
    assert integrity.failures() == []


def test_engine_validation_off_identity():
    """validation="off" output is byte-identical to the pre-validation
    pipeline, and taps trace to nothing without an armed plan."""
    x = _eng_x()
    y_off, aux_off = zebra_site(x, _ENG.replace(backend="stream"), site="m")
    y_on, aux_on = zebra_site(
        x, _ENG.replace(backend="stream", validation="structural"), site="m")
    np.testing.assert_array_equal(np.asarray(y_off), np.asarray(y_on))
    assert int(aux_off.measured_bytes) == int(aux_on.measured_bytes)


def test_engine_detection_under_jit():
    """The whole validated pipeline jits; the recovery branch's
    debug.callback fires at RUN time only on faulted executions."""
    x = _eng_x()
    cfg = _ENG.replace(backend="stream", validation="structural")
    with inject(Fault(kind="bitflip", site="engine:j", times=-1)):
        f = jax.jit(lambda v: zebra_site(v, cfg, site="j")[0])
        integrity.clear_failures()
        y = jax.block_until_ready(f(x))
        assert integrity.failures() == ["engine:j"]
        integrity.clear_failures()
        jax.block_until_ready(f(x))          # cached trace, fault re-bites
        assert integrity.failures() == ["engine:j"]
    y_clean, _ = zebra_site(x, cfg.replace(validation="off"), site="j")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_clean))


# ---------------------------------------------------------------------------
# Serve boundary (concrete CompressedMap handoff, per-leaf dense fallback)
# ---------------------------------------------------------------------------

def _cache_tree():
    k1 = jax.random.normal(jax.random.PRNGKey(2), (64, 256), jnp.float32)
    k2 = jax.random.normal(jax.random.PRNGKey(3), (64, 256), jnp.float32)
    zero = np.ones((8, 2), bool)
    zero[1::2] = False                        # kill half the blocks
    mask = jnp.repeat(jnp.repeat(jnp.asarray(zero), 8, 0), 128, 1)
    return {"a": {"k": k1 * mask}, "b": {"v": k2 * mask}}


@pytest.mark.parametrize("kind,level", [
    ("bitflip", "structural"), ("truncate", "structural"),
    ("nan", "structural"), ("count", "structural"), ("value", "checksum"),
])
def test_serve_handoff_detect_recover(kind, level):
    from repro.compress import compress_tree, decompress_tree
    from repro.launch.serve import validate_state_ingest
    dense = _cache_tree()
    ctree = compress_tree(dense, bs=8, bc=128,
                          checksum=(level == "checksum"))
    with inject(Fault(kind=kind, site="serve", arg=1)) as plan:
        recovered, n_bad = validate_state_ingest(ctree, dense, level)
    assert plan.injected == [(kind, "serve")]
    assert n_bad == 1, "exactly the corrupted leaf recovers dense"
    out = decompress_tree(recovered)
    for key_path in (("a", "k"), ("b", "v")):
        want = dense[key_path[0]][key_path[1]]
        got = out[key_path[0]][key_path[1]]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_serve_handoff_clean_passthrough():
    from repro.compress import CompressedMap, compress_tree
    from repro.launch.serve import validate_state_ingest
    dense = _cache_tree()
    ctree = compress_tree(dense, bs=8, bc=128, checksum=True)
    out, n_bad = validate_state_ingest(ctree, dense, "checksum")
    assert n_bad == 0
    leaves = jax.tree_util.tree_leaves(
        out, is_leaf=lambda l: isinstance(l, CompressedMap))
    assert all(isinstance(l, CompressedMap) for l in leaves)


def test_corrupt_map_each_kind_raises():
    from repro.compress import compress
    from repro.compress.integrity import attach_checksum, validate_map
    x = np.asarray(_cache_tree()["a"]["k"])
    cm = attach_checksum(compress(jnp.asarray(x), bs=8, bc=128,
                                  use_kernel=False))
    validate_map(cm, level="checksum")        # clean passes
    for kind in ("bitflip", "truncate", "nan", "count", "value"):
        bad = corrupt_map(cm, kind, arg=2)
        with pytest.raises(CorruptStream):
            validate_map(bad, level="checksum", site=kind)


# ---------------------------------------------------------------------------
# Checkpoint boundary (CRC manifest + newest -> older fallback)
# ---------------------------------------------------------------------------

def _save_steps(ckpt, steps):
    for s in steps:
        state = {"w": jnp.full((16, 16), float(s)), "s": jnp.int32(s)}
        ckpt.save(s, state, {"loader_step": s})
    ckpt.wait()
    return state


def test_ckpt_corrupt_newest_falls_back(tmp_path):
    from repro.checkpoint import CheckpointManager
    ckpt = CheckpointManager(str(tmp_path), keep_last=3)
    like = _save_steps(ckpt, [2, 4, 6])
    corrupt_file(os.path.join(str(tmp_path), "step_6", "shard_0.npz"))
    step, tree, extra = ckpt.restore(like)
    assert step == 4, "corrupt newest must fall back to the older step"
    assert float(np.asarray(tree["w"])[0, 0]) == 4.0
    assert extra["loader_step"] == 4


def test_ckpt_explicit_step_never_falls_back(tmp_path):
    from repro.checkpoint import CheckpointManager
    ckpt = CheckpointManager(str(tmp_path), keep_last=3)
    like = _save_steps(ckpt, [2, 4])
    corrupt_file(os.path.join(str(tmp_path), "step_4", "shard_0.npz"))
    # the flip is caught either by the zip member CRC on read or by the
    # manifest leaf CRC — both surface as CorruptStream naming the leaf
    with pytest.raises(CorruptStream, match="CRC mismatch|unreadable"):
        ckpt.restore(like, step=4)


def test_ckpt_whole_chain_corrupt_raises(tmp_path):
    from repro.checkpoint import CheckpointManager
    ckpt = CheckpointManager(str(tmp_path), keep_last=3)
    like = _save_steps(ckpt, [2, 4])
    for s in (2, 4):
        corrupt_file(os.path.join(str(tmp_path), f"step_{s}", "shard_0.npz"))
    with pytest.raises(CorruptStream, match="no restorable checkpoint"):
        ckpt.restore(like)


def test_ckpt_truncated_manifest_falls_back(tmp_path):
    from repro.checkpoint import CheckpointManager
    ckpt = CheckpointManager(str(tmp_path), keep_last=3)
    like = _save_steps(ckpt, [2, 4])
    mpath = os.path.join(str(tmp_path), "step_4", "manifest.json")
    with open(mpath, "r+") as f:
        f.truncate(10)                       # killed mid-write
    step, tree, _ = ckpt.restore(like)
    assert step == 2


def test_ckpt_pre_checksum_manifest_restores(tmp_path):
    """Manifests written before the CRC scheme (no ``checksums`` key)
    restore unchanged — no forced re-save of old checkpoints."""
    from repro.checkpoint import CheckpointManager
    ckpt = CheckpointManager(str(tmp_path), keep_last=3)
    like = _save_steps(ckpt, [2])
    mpath = os.path.join(str(tmp_path), "step_2", "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    del manifest["checksums"]
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    step, tree, _ = ckpt.restore(like)
    assert step == 2 and float(np.asarray(tree["w"])[0, 0]) == 2.0


def test_ckpt_acts_restore_validates(tmp_path):
    """A flipped on-disk index bit would silently relocate every later
    payload block; restore_acts' structural check names it instead."""
    from repro.checkpoint import CheckpointManager
    ckpt = CheckpointManager(str(tmp_path), keep_last=2)
    acts = {"h": np.asarray(_cache_tree()["a"]["k"])}
    ckpt.save_acts(3, acts, compressed=True, bs=8, bc=128)
    out = ckpt.restore_acts(3)               # structural validation default
    np.testing.assert_array_equal(out["h"], acts["h"])
    path = os.path.join(str(tmp_path), "acts_3.npz")
    data = dict(np.load(path).items())       # tamper the stored index: one
    idx = np.array(data["h/index"])          # flipped bit != n_live popcount
    idx[0] ^= 1
    data["h/index"] = idx
    np.savez(path, **data)
    with pytest.raises(CorruptStream, match="popcount"):
        ckpt.restore_acts(3)
    assert "h" in ckpt.restore_acts(3, validation="off")  # opt-out preserved


# ---------------------------------------------------------------------------
# Supervisor policies
# ---------------------------------------------------------------------------

def _counting_iter():
    class It:
        i = 0
        def __next__(self):
            self.i += 1
            return jnp.full((4,), float(self.i))
        def restore(self, step):
            self.i = int(step)
    return It()


def _plain_step(state, batch):
    return ({"w": state["w"] + batch.mean(), "step": state["step"] + 1},
            {"loss": jnp.float32(1.0)})


def test_supervisor_failure_decay(tmp_path):
    """One transient blip must not count against max_failures forever."""
    cfg = FTConfig(ckpt_dir=str(tmp_path), ckpt_every=2, max_failures=2,
                   failure_decay_steps=3, backoff_base_s=0.0)
    sup = StepSupervisor(cfg)
    step_fn = crashing_step(_plain_step, crash_at=5)
    state = {"w": jnp.float32(0.0), "step": jnp.int32(0)}
    it = _counting_iter()
    _, step = sup.run(state, step_fn, it, steps=12,
                      loader_state_fn=lambda: it.i)
    assert step == 12
    assert sup.failures == 0, "sustained success must decay the counter"
    assert len(sup.failure_log) == 1
    assert sup.failure_log[0]["policy"] == "restore-retry"


def test_supervisor_unclassified_reraises(tmp_path):
    cfg = FTConfig(ckpt_dir=str(tmp_path), ckpt_every=2)
    sup = StepSupervisor(cfg)
    step_fn = crashing_step(_plain_step, crash_at=4,
                            exc=lambda: ValueError("typo in the model"))
    state = {"w": jnp.float32(0.0), "step": jnp.int32(0)}
    with pytest.raises(ValueError, match="typo"):
        sup.run(state, step_fn, _counting_iter(), steps=8)
    assert sup.failures == 0, "bugs are not counted as faults"


def test_supervisor_poison_skips_batch(tmp_path):
    cfg = FTConfig(ckpt_dir=str(tmp_path), ckpt_every=100,
                   max_poison_skips=2)
    sup = StepSupervisor(cfg)
    def step_fn(state, batch):
        new = {"w": state["w"] + 1.0, "step": state["step"] + 1}
        loss = jnp.where(jnp.isclose(batch.mean(), 4.0), jnp.nan, 1.0)
        return new, {"loss": jnp.float32(loss)}
    state = {"w": jnp.float32(0.0), "step": jnp.int32(0)}
    final, step = sup.run(state, step_fn, _counting_iter(), steps=8)
    assert step == 8
    assert len(sup.skipped_batches) == 1
    assert sup.failures == 0, "a poison batch is not a restore-class failure"
    # the poisoned update was discarded: 7 applied updates, not 8
    assert float(final["w"]) == 7.0


def test_supervisor_all_poison_gives_up(tmp_path):
    cfg = FTConfig(ckpt_dir=str(tmp_path), max_poison_skips=2)
    sup = StepSupervisor(cfg)
    def step_fn(state, batch):
        return state, {"loss": jnp.float32(jnp.nan)}
    with pytest.raises(PoisonBatch):
        sup.run({"w": jnp.float32(0.0)}, step_fn, _counting_iter(), steps=8)
    assert len(sup.skipped_batches) == cfg.max_poison_skips + 1


def test_supervisor_device_loss_hook(tmp_path):
    cfg = FTConfig(ckpt_dir=str(tmp_path), ckpt_every=100)
    sup = StepSupervisor(cfg)
    step_fn = crashing_step(_plain_step, crash_at=3,
                            exc=lambda: DeviceLoss("lost a host"))
    calls = []
    def remesh(state):
        calls.append(1)
        return state
    state = {"w": jnp.float32(0.0), "step": jnp.int32(0)}
    _, step = sup.run(state, step_fn, _counting_iter(), steps=6,
                      on_device_loss=remesh)
    assert step == 6 and calls == [1]
    assert sup.failure_log[0]["policy"] == "remesh"


def test_straggler_enters_window():
    """The flagged dt must join the trailing window so a sustained
    slowdown re-baselines instead of flagging forever."""
    sup = StepSupervisor(FTConfig(straggler_window=10, straggler_zscore=3.0))
    for _ in range(10):
        sup.check_straggler(0.1)
    assert sup.check_straggler(5.0)
    assert sup.times[-1] == 5.0
    # window poisoned toward the new regime: repeating the slow dt soon
    # stops being an outlier
    flags = [sup.check_straggler(5.0) for _ in range(10)]
    assert not flags[-1]


def test_backoff_monotone_and_bounded(tmp_path):
    cfg = FTConfig(ckpt_dir=str(tmp_path), backoff_base_s=0.1,
                   backoff_cap_s=0.4, backoff_jitter=0.25)
    sup = StepSupervisor(cfg)
    lows, highs = [], []
    for k in (1, 2, 3, 4):
        sup.failures = k
        base = min(0.1 * 2 ** (k - 1), 0.4)
        lows.append(base * 0.75)
        highs.append(base * 1.25)
        d = sup._backoff()
        assert lows[-1] <= d <= highs[-1]


# ---------------------------------------------------------------------------
# Ring collectives boundary (8-device subprocess)
# ---------------------------------------------------------------------------

_RING_SCRIPT = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from repro.distributed import collectives as coll
from repro.compress import integrity
from repro.ft import inject, Fault

mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
M, K, BS, BC = 64, 256, 8, 128
NM, NK = M // BS, K // BC
rng = np.random.default_rng(3)
sh = rng.normal(size=(4, M, K)).astype(np.float32)
keep = rng.random((4, NM, NK)) < 0.4
sh = sh * np.repeat(np.repeat(keep, BS, 1), BC, 2)
sh[2] = 0.0                                  # all-dead shard edge case
X = jnp.asarray(sh.reshape(4 * M, K))
out = {}

sm = lambda f, outs: jax.jit(coll.shard_map_compat(
    f, mesh, in_specs=(P("model", None),), out_specs=outs))

def mk_ag(level):
    def ag(x):
        y, link = coll.zebra_all_gather(x, "model", bs=BS, bc=BC, tiled=True,
                                        validation=level, site="t")
        return y, lax.psum(link.moved, "model")
    return sm(ag, (P(), P()))

y_ref = sm(lambda x: lax.all_gather(x, "model", axis=0, tiled=True), P())(X)
y0, moved0 = mk_ag("structural")(X)
out["clean"] = {"parity": bool((np.asarray(y0) == np.asarray(y_ref)).all()),
                "moved": int(moved0)}

for level in ("structural", "checksum"):
    for kind, arg in (("drop_hop", 2), ("drop_hop", 3)):
        integrity.clear_failures()
        with inject(Fault(kind=kind, site="ring:t", arg=arg)) as plan:
            y2, moved2 = mk_ag(level)(X)
            jax.block_until_ready(y2)
        out[f"ag_{kind}{arg}_{level}"] = {
            "injected": len(plan.injected), "detected": len(integrity.failures()),
            "parity": bool((np.asarray(y2) == np.asarray(y_ref)).all()),
            "retry_bytes": int(moved2) > int(moved0)}

def mk_ps(level):
    def ps(x):
        y, union, link = coll.zebra_psum_stream(x, "model", bs=BS, bc=BC,
                                                validation=level, site="p")
        return y, lax.psum(link.moved, "model")
    return sm(ps, (P("model", None), P()))

yp_ref = sm(lambda x: lax.psum(x, "model"), P("model", None))(X)
yp0, _ = mk_ps("checksum")(X)
out["psum_clean"] = {"close": bool(np.allclose(np.asarray(yp0),
                                               np.asarray(yp_ref), atol=1e-4))}
integrity.clear_failures()
with inject(Fault(kind="drop_hop", site="ring:p", arg=1)) as plan:
    yp2, _ = mk_ps("checksum")(X)
    jax.block_until_ready(yp2)
out["psum_drop"] = {
    "injected": len(plan.injected), "detected": len(integrity.failures()),
    "parity": bool((np.asarray(yp2) == np.asarray(yp_ref)).all())}

# bitmap-union edge: one shard dead -> union is the union of the others
def un(x):
    y, union, link = coll.zebra_psum_stream(x, "model", bs=BS, bc=BC,
                                            validation="structural")
    return union, lax.psum(link.moved, "model")
union, _ = sm(un, (P(), P()))(X)
want_union = (np.abs(sh).reshape(4, NM, BS, NK, BC).max((2, 4)) > 0).any(0)
out["union_edge"] = {"match": bool((np.asarray(union).astype(bool)
                                    == want_union).all())}
print("RESULT " + json.dumps(out))
"""


def test_ring_chaos_8dev():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", _RING_SCRIPT], env=env,
                       cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["clean"]["parity"], "clean validated gather must stay bitwise"
    for key in ("ag_drop_hop2_structural", "ag_drop_hop3_structural",
                "ag_drop_hop2_checksum", "ag_drop_hop3_checksum"):
        cell = out[key]
        assert cell["injected"] == 1, key
        assert cell["detected"] >= 1, f"{key}: fault not detected"
        assert cell["parity"], f"{key}: recovery not bitwise"
        assert cell["retry_bytes"], f"{key}: dense retry must be accounted"
    assert out["psum_clean"]["close"]
    assert out["psum_drop"]["detected"] >= 1
    assert out["psum_drop"]["parity"], \
        "psum recovery falls back to dense lax.psum (bitwise to reference)"
    assert out["union_edge"]["match"]
