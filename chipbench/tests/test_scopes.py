"""Device time by the program's named scopes, and the gate readers:
on a hand-made record with known answers, and on a record whose ops are
the instructions of the tiny CNN cell's compiled forward."""
import contextlib

import jax
import pytest

from chipbench import bench
from chipbench.metrics.lib import scopes
from chipbench.tests import tiny

US = 1e3        # ns per us

HLO = """HloModule jit__lambda_, entry_computation_layout={...}

%fused_computation (param_0: bf16[8]) -> bf16[8] {
  %param_0 = bf16[8]{0} parameter(0)
  ROOT %maximum.1 = bf16[8]{0} maximum(%param_0, %param_0), metadata={op_name="jit(<lambda>)/stem/max"}
}

%fused_computation.2 (param_0.1: bf16[8]) -> bf16[8] {
  %param_0.1 = bf16[8]{0} parameter(0)
  ROOT %select.2 = bf16[8]{0} select(%param_0.1, %param_0.1, %param_0.1), metadata={op_name="jit(<lambda>)/s1b0/zebra.z5/select_n"}
}

%fused_computation.1 (param_0.2: bf16[8]) -> bf16[8] {
  %param_0.2 = bf16[8]{0} parameter(0)
  %fusion.7 = bf16[8]{0} fusion(%param_0.2), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(<lambda>)/s1b0/zebra.z5/select_n"}
  ROOT %convolution.1 = bf16[8]{0} convolution(%fusion.7, %param_0.2), metadata={op_name="jit(<lambda>)/s1b1/conv_general_dilated"}
}

ENTRY %main.9 (x.1: bf16[8]) -> (bf16[8], s32[]) {
  %x.1 = bf16[8]{0:T(256)} parameter(0), metadata={op_name="x"}
  %fusion.3 = bf16[8]{0:T(256)} fusion(%x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(<lambda>)/s0b1/zebra.z3/reduce_max" stack_frame_id=4}
  %conv.2 = bf16[8]{0:T(256)} convolution(%fusion.3, %x.1), metadata={op_name="jit(<lambda>)/s0b1/conv_general_dilated"}
  %copy.1 = bf16[8]{0:T(256)} copy(%conv.2)
  %fusion.5 = bf16[8]{0:T(256)} fusion(%copy.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(<lambda>)/s1b1/conv_general_dilated"}
  %zebra_mask_pack.2 = s32[] custom-call(%copy.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(<lambda>)/head/jit(zebra_mask_pack)/zebra_mask_pack/pallas_call"}
  ROOT %tuple.4 = (bf16[8]{0}, s32[]) tuple(%copy.1, %zebra_mask_pack.2)
}
"""

# window 0..10 us: the gate 1-3 and (clipped) 9-12, a convolution 2-5
# that overlaps it, a copy 6-7, and a loop whose body's ops are counted
RECORD = {
    "window": [0.0, 10 * US],
    "devices": {"0": {
        "ops": [["fusion.3 bf16[8]", 1 * US, 2 * US],
                ["conv.2 bf16[8]", 2 * US, 3 * US],
                ["copy.1 bf16[8]", 6 * US, 1 * US],
                ["fusion.3 bf16[8]", 9 * US, 3 * US],
                ["while.7", 0.0, 10 * US]],
        "modules": []}},
    "spans": [],
}


def test_op_scopes_from_compiled_text():
    got = scopes.op_scopes(HLO)
    assert got["fusion.3 bf16[8]"] == "jit(<lambda>)/s0b1/zebra.z3/reduce_max"
    assert got["maximum.1 bf16[8]"] == "jit(<lambda>)/stem/max"
    assert got["copy.1 bf16[8]"] == ""
    assert got["x.1 bf16[8]"] == "x"
    assert got["tuple.4"] == ""
    assert got["zebra_mask_pack.2 s32[]"].endswith("/zebra_mask_pack/pallas_call")


def test_op_contents_reach_into_nested_fusions():
    got = scopes.op_contents(HLO)
    assert got["fusion.3 bf16[8]"] == {
        "jit(<lambda>)/s0b1/zebra.z3/reduce_max", "jit(<lambda>)/stem/max"}
    assert got["fusion.5 bf16[8]"] == {
        "jit(<lambda>)/s1b1/conv_general_dilated",
        "jit(<lambda>)/s1b0/zebra.z5/select_n"}
    assert got["copy.1 bf16[8]"] == frozenset()
    assert got["conv.2 bf16[8]"] == {"jit(<lambda>)/s0b1/conv_general_dilated"}


@pytest.mark.parametrize("op_name,scope,want", [
    ("jit(f)/s0b1/zebra.z3/reduce_max", "zebra", True),
    ("jit(f)/zebra/mul", "zebra", True),
    ("jit(f)/head/jit(zebra_mask_pack)/zebra_mask_pack/pallas_call", "zebra",
     False),
    ("jit(f)/s0b1/zebra.z3/reduce_max", "s0b1", True),
    ("jit(f)/s0b10/conv", "s0b1", False),
    ("", "zebra", False),
])
def test_under_matches_whole_path_components(op_name, scope, want):
    assert scopes.under(op_name, scope) is want


def test_scope_seconds_on_a_hand_record():
    s = scopes.op_scopes(HLO)
    assert scopes.covers(RECORD, s)
    # gate 1-3 and 9-10 us; its block adds the convolution (union 1-5,
    # 9-10 us); the loop is left out
    assert scopes.scope_seconds(RECORD, s, "zebra") == pytest.approx(3e-6)
    assert scopes.scope_seconds(RECORD, s, "s0b1") == pytest.approx(5e-6)
    assert scopes.scope_seconds(RECORD, s, "head") == 0.0
    other = {**RECORD, "devices": {"0": {"ops": RECORD["devices"]["0"]["ops"]
                                         + [["fusion.9 f32[4]", 0, 1]],
                                         "modules": []}}}
    assert not scopes.covers(other, s)


def test_touch_seconds_count_fused_gate_work_whole():
    """The ceiling counts the convolution fusion that a gate's select was
    fused into (6-8 us), which the floor credits to the convolution."""
    rec = {**RECORD, "devices": {"0": {
        "ops": RECORD["devices"]["0"]["ops"] + [["fusion.5 bf16[8]", 6 * US,
                                                 2 * US]],
        "modules": []}}}
    s, c = scopes.op_scopes(HLO), scopes.op_contents(HLO)
    assert scopes.covers(rec, s)
    assert scopes.scope_seconds(rec, s, "zebra") == pytest.approx(3e-6)
    assert scopes.touch_seconds(rec, c, "zebra") == pytest.approx(5e-6)
    assert scopes.scope_seconds(rec, s, "stem") == 0.0
    assert scopes.touch_seconds(rec, c, "stem") == pytest.approx(3e-6)
    assert scopes.touch_seconds(rec, c, "head") == 0.0


def _tiny_data():
    cell = tiny.cell("tiny-images")
    return {"config": cell.config, "traffic": cell.traffic, "peak": None}


def _record_of(names):
    """One 1 us op per name, back to back, in a window that holds them."""
    ops = [[n, i * US, US] for i, n in enumerate(names)]
    return {"window": [0.0, len(ops) * US],
            "devices": {"0": {"ops": ops, "modules": []}}, "spans": []}


@pytest.fixture
def fresh_scopes():
    scopes._cnn_hlo.cache_clear()
    yield
    scopes._cnn_hlo.cache_clear()


def test_gate_readers_on_the_tiny_cells_program(fresh_scopes):
    data = _tiny_data()
    ops = scopes.cnn_scopes(data)
    sites = {c for o in ops.values() for c in o.split("/")
             if c.startswith("zebra.")}
    assert sites == {f"zebra.z{i}" for i in range(17)}
    names = sorted(ops)
    gate = [n for n in names if scopes.under(ops[n], "zebra")]
    assert 0 < len(gate) < len(names)
    batch = data["traffic"]["batch"]
    data.update(trace=_record_of(names),
                counters={"images": 4 * batch, "window_s": 1.0})
    share = bench.reader("gate_share.cnn")(data)
    ms = bench.reader("gate_ms_per_batch.cnn")(data)
    assert share == pytest.approx(100.0 * len(gate) / len(names))
    contents = scopes.op_contents(scopes.cnn_hlo(data))
    touch = [n for n in names
             if any(scopes.under(o, "zebra") for o in contents[n])]
    assert set(gate) <= set(touch)
    assert bench.reader("gate_touch_share.cnn")(data) == pytest.approx(
        100.0 * len(touch) / len(names))
    assert ms == pytest.approx(len(gate) * 1e-3 / 4)
    data["counters"] = {"images": 0, "window_s": 1.0}
    assert bench.reader("gate_ms_per_batch.cnn")(data) is None


GATE_READERS = ("gate_share.cnn", "gate_ms_per_batch.cnn",
                "gate_touch_share.cnn")


def test_gate_readers_read_nothing_they_cannot_attribute(fresh_scopes,
                                                         monkeypatch):
    data = _tiny_data()
    names = sorted(scopes.cnn_scopes(data))
    data["counters"] = {"images": 4 * data["traffic"]["batch"],
                        "window_s": 1.0}
    # an op the program does not have: the record is another program's
    data["trace"] = _record_of(names + ["fusion.99999 f32[3]"])
    for m in GATE_READERS:
        assert bench.reader(m)(data) is None
    # a program that names no site (as before the sites were scoped)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    scopes._cnn_hlo.cache_clear()
    jax.clear_caches()
    data["trace"] = _record_of(sorted(scopes.cnn_scopes(data)))
    for m in GATE_READERS:
        assert bench.reader(m)(data) is None


def _forward(data):
    """The tiny cell's forward compiled as its window compiles it."""
    from chipbench.families.cnn_infer import images, program
    _, _, init, fwd = program(data["config"])
    key = jax.random.PRNGKey(0)
    x = jax.eval_shape(lambda k: images(
        k, {"staged_batches": 1, "batch": data["traffic"]["batch"]},
        data["config"])[0], key)
    return fwd.lower(jax.eval_shape(init, key), x).compile()


def test_readers_see_this_builds_scopes_past_a_stale_cache(fresh_scopes,
                                                          tmp_path,
                                                          monkeypatch):
    """The persistent cache keys programs without their metadata, so the
    window's program can come back with an earlier build's scope names;
    the readers still read this build's, for the same instructions."""
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    data = _tiny_data()
    try:
        for k, v in zip(keys, (str(tmp_path), True, 0.0, 0)):
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        with monkeypatch.context() as m:    # an earlier build: no scopes
            m.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
            _forward(data)
        jax.clear_caches()
        ran = _forward(data).as_text()
        assert "/zebra.z" not in ran        # the earlier build's names
        ops = scopes.cnn_scopes(data)
        assert {c for o in ops.values() for c in o.split("/")
                if c.startswith("zebra.")} == {f"zebra.z{i}"
                                               for i in range(17)}
        assert set(ops) == set(scopes.op_scopes(ran))
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        jax.clear_caches()
