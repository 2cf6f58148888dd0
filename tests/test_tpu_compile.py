"""The main-path kernels compile for a TPU v5e at published widths.

Each test compiles against a described ``v5e:2x2`` topology — no chip is
attached, and nothing runs — with ``jax.default_backend`` steered to
``"tpu"`` so ``kernels.platform`` picks the compiled Pallas forms. This
catches what interpret mode cannot: blocks Mosaic will not lay out,
scratch or SMEM past the chip's limits, programs past its HBM.

Shapes are starcoder2-15b's FFN (a 4096-token prefill: (4096, 24576)
hidden map, 24576 x 6144 down projection), an f32 (4096, 1024) map, and
ResNet-18's first Tiny-ImageNet site at batch 128. All compiles stay in this file, so one
xdist worker owns the TPU compiler.
"""
from __future__ import annotations

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

M, K, N = 4096, 24576, 6144
BS, BC = 8, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Trace as on a TPU, with the persistent compilation cache off (a
    compile for a described chip cannot be read back) and no trace
    shared with the interpret-mode tests in either direction."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("k,dtype", [(K, jnp.bfloat16), (1024, jnp.float32)])
def test_mask_pack_compiles(one_chip, as_tpu, k, dtype):
    from repro.kernels.mask_pack import zebra_mask_pack
    x = jax.ShapeDtypeStruct((M, k), dtype, sharding=one_chip)
    hlo = _compile(lambda x: zebra_mask_pack(x, t_obj=1.6), x)
    assert hlo.count("tpu_custom_call") >= 2   # comparator + gather-pack


def test_mask_compiles_at_engine_tiles(one_chip, as_tpu):
    from repro.core import ZebraConfig
    from repro.kernels.zebra_mask import zebra_mask
    tm, tk = ZebraConfig().tiles_for(M, K, BS, BC, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((M, K), jnp.bfloat16, sharding=one_chip)
    hlo = _compile(lambda x: zebra_mask(x, t_obj=1.6, tm=tm, tk=tk), x)
    assert "tpu_custom_call" in hlo


def test_spmm_cs_compiles(one_chip, as_tpu):
    from repro.kernels.spmm_cs import zebra_spmm_cs
    payload = jax.ShapeDtypeStruct(((M // BS) * (K // BC), BS, BC),
                                   jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((K, N), jnp.bfloat16, sharding=one_chip)
    bitmap = jax.ShapeDtypeStruct((M // BS, K // BC), jnp.int8,
                                  sharding=one_chip)
    assert "tpu_custom_call" in _compile(zebra_spmm_cs, payload, w, bitmap)


def test_unpack_compiles(one_chip, as_tpu):
    from repro.kernels.pack import zebra_unpack
    payload = jax.ShapeDtypeStruct(((M // BS) * (K // BC), BS, BC),
                                   jnp.bfloat16, sharding=one_chip)
    bitmap = jax.ShapeDtypeStruct((M // BS, K // BC), jnp.int8,
                                  sharding=one_chip)
    assert "tpu_custom_call" in _compile(zebra_unpack, payload, bitmap)


def test_nchw_site_compiles_to_the_reference_path(one_chip, as_tpu):
    """4x4 NCHW blocks cannot fill a (8, 128) vreg tile: on a TPU the
    engine resolves the site to its reference masked map and says so in
    the label."""
    from repro.core import ZebraConfig
    from repro.core.engine import zebra_site
    cfg = ZebraConfig(t_obj=1.2, use_tnet=False, backend="stream",
                      mode="infer")
    x = jax.ShapeDtypeStruct((128, 64, 64, 64), jnp.float32,
                             sharding=one_chip)
    labels = []

    def site(x):
        y, aux = zebra_site(x, cfg, site="z0", layout="nchw")
        labels.append(aux.backend)
        return y, aux.measured_bytes

    hlo = _compile(site, x)
    assert labels == ["reference(narrow-blocks)"]
    assert "tpu_custom_call" not in hlo


_METADATA = re.compile(r", metadata=\{[^}]*\}")
_LOCATIONS = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*", re.M)


def _r18_forward_hlo(one_chip) -> str:
    """The compiled text of the ``r18-infer`` forward (ResNet-18 on
    Tiny-ImageNet, batch 128, 64x64, bfloat16 maps, 4x4 blocks)."""
    from repro.core import ZebraConfig
    from repro.models.cnn import build
    zc = ZebraConfig(t_obj=1.2, block_hw=4, backend="stream",
                     use_tnet=False, mode="infer")
    m = build("resnet18", num_classes=200, in_hw=64)
    v = jax.eval_shape(lambda k: m.init(k, zc), jax.random.PRNGKey(0))
    v = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), v)
    x = jax.ShapeDtypeStruct((128, 3, 64, 64), jnp.bfloat16,
                             sharding=one_chip)
    return _compile(lambda v, x: m.apply(v, x, False, zc)[0], v, x)


def test_scopes_leave_the_chip_program_unchanged(one_chip, as_tpu,
                                                 monkeypatch):
    """The ResNet-18 Tiny-ImageNet forward at batch 128 with bfloat16 maps,
    compiled for a v5e, is the same program with and without its named
    scopes once metadata is stripped: the scopes name ops, and change no
    instruction, fusion or layout."""
    with_scopes = _r18_forward_hlo(one_chip)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    without = _r18_forward_hlo(one_chip)
    assert "/s0b0/zebra.z1/" in with_scopes and "/zebra.z" not in without
    code = [_METADATA.sub("", _LOCATIONS.sub("", h))
            for h in (with_scopes, without)]
    assert "ENTRY" in code[0] and "FileNames" not in code[0]
    assert code[0] == code[1]


def _kernel_cases():
    """Each Zebra kernel launched outside the jitted wrapper that usually
    encloses it, so only the kernel's own ``name=`` can name it: (call,
    argument shapes as (shape, dtype), the names of its custom calls)."""
    from repro.kernels.mask_pack import zebra_mask_pack
    from repro.kernels.pack import zebra_pack, zebra_unpack
    from repro.kernels.spmm_cs import zebra_spmm_cs
    from repro.kernels.zebra_mask import zebra_mask
    from repro.kernels.zebra_spmm import zebra_spmm
    m, k, n = 512, 1024, 256
    x, w = ((m, k), jnp.bfloat16), ((k, n), jnp.bfloat16)
    bitmap = ((m // BS, k // BC), jnp.int8)
    payload = (((m // BS) * (k // BC), BS, BC), jnp.bfloat16)
    return {
        "zebra_mask": (lambda x: zebra_mask.__wrapped__(
            x, t_obj=1.6, bs=BS, bc=BC), (x,), ["zebra_mask"]),
        "zebra_pack": (lambda x, b: zebra_pack.__wrapped__(
            x, b, bs=BS, bc=BC), (x, bitmap), ["zebra_pack"]),
        "zebra_unpack": (lambda p, b: zebra_unpack.__wrapped__(
            p, b, bs=BS, bc=BC), (payload, bitmap), ["zebra_unpack"]),
        "zebra_mask_pack": (lambda x: zebra_mask_pack.__wrapped__(
            x, t_obj=1.6, bs=BS, bc=BC), (x,), ["zebra_mask_pack"] * 2),
        "zebra_spmm": (lambda x, w, b: zebra_spmm.__wrapped__(
            x, w, b, bs=BS, bc=BC), (x, w, bitmap), ["zebra_spmm"]),
        "zebra_spmm_cs": (lambda p, w, b: zebra_spmm_cs.__wrapped__(
            p, w, b, bs=BS, bc=BC), (payload, w, bitmap), ["zebra_spmm_cs"]),
        "zebra_spmm_cs-expand": (lambda p, w, b: zebra_spmm_cs.__wrapped__(
            p, w, b, bs=BS, bc=BC, payload_windows=False),
            (payload, w, bitmap), ["zebra_spmm_cs"]),
    }


_CUSTOM_CALL = re.compile(r"^\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = [^\n]*"
                          r"custom_call_target=\"tpu_custom_call\"", re.M)


@pytest.mark.parametrize("case", ["zebra_mask", "zebra_pack", "zebra_unpack",
                                  "zebra_mask_pack", "zebra_spmm",
                                  "zebra_spmm_cs", "zebra_spmm_cs-expand"])
def test_kernel_is_named_in_the_compiled_program(one_chip, as_tpu, case):
    """The compiled program (and so a device trace) names each Zebra
    kernel's custom call after the kernel, whatever function launched it:
    the names the roofline readers match."""
    call, args, names = _kernel_cases()[case]
    hlo = _compile(call, *(jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                           for s, d in args))
    assert sorted(_CUSTOM_CALL.findall(hlo)) == names


_ENTRY_OP = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.+?) "
                       r"([\w\-]+)\(([^)]*)\)(.*)$", re.M)
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_SITE = re.compile(r'op_name="[^"]*/zebra\.(z\d+)/')


def test_each_cnn_site_is_one_block_max_and_one_broadcast(one_chip, as_tpu):
    """The ResNet-18 Tiny-ImageNet forward at batch 128 with bfloat16 maps,
    compiled for a v5e: each of the 17 Zebra sites runs at most two ops of
    its own, one fusion of the windowed block max with the threshold
    compare and one broadcast of the keep map to the map's shape. No site
    reduces a 6-D view of its map (which writes the block max) or
    broadcasts the keep map through a 5-D intermediate."""
    hlo = _r18_forward_hlo(one_chip)
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    shape, sites = {}, {}
    for name, typ, opcode, operands, rest in _ENTRY_OP.findall(entry):
        array = _ARRAY.match(typ)
        if array:
            dims = array.group(2)
            shape[name] = (array.group(1), len(dims.split(",")) if dims else 0)
        site = _SITE.search(rest)
        if site and opcode != "bitcast":
            sites.setdefault(site.group(1), []).append(
                (name, opcode, operands.split(",")[0].strip().lstrip("%")))
    assert sorted(sites) == sorted(f"z{i}" for i in range(17))
    for site, ops in sites.items():
        for name, opcode, first in ops:
            assert not (opcode == "reduce" and shape[first][1] == 6), (
                site, name)
            assert not (opcode == "broadcast"
                        and shape[name] == ("pred", 5)), (site, name)
        assert len(ops) <= 2, (site, ops)
