"""Named scopes in the compiled programs: every Zebra site runs under
``zebra.<site>`` and every CNN op under exactly one layer scope, read from
the ``op_name`` metadata of the compiled HLO; the scopes change nothing
else in the program. Also the site engine's log: a degrade is logged once
at INFO, and a trace logs nothing else."""
import contextlib
import logging
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import ZebraConfig, zebra_site
from repro.models.cnn import build

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_METADATA = re.compile(r", metadata=\{[^}]*\}")
_LOCATIONS = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*", re.M)
INFER = ZebraConfig(t_obj=1.2, block_hw=4, backend="reference",
                    use_tnet=False, mode="infer")

# model -> (input size, Zebra sites); both through ``ResNet.apply``
MODELS = {"resnet18": (16, 17), "resnet56": (16, 55)}
LAYERS = r"stem|s\db\d|head"


def _forward_hlo(name: str) -> str:
    hw = MODELS[name][0]
    m = build(name, num_classes=10, in_hw=hw, width_mult=0.125)
    v = jax.eval_shape(lambda k: m.init(k, INFER), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2, 3, hw, hw), jnp.bfloat16)
    fwd = jax.jit(lambda v, x: m.apply(v, x, False, INFER)[0])
    return fwd.lower(v, x).compile().as_text()


def _code(hlo: str) -> str:
    """Compiled HLO text without metadata or the source-location tables."""
    return _METADATA.sub("", _LOCATIONS.sub("", hlo))


def _program_ops(hlo: str) -> list[str]:
    """op_names of the traced program's ops (parameters are named by
    their argument path instead)."""
    return [o for o in _OP_NAME.findall(hlo) if o.startswith("jit(")]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_every_op_under_one_layer_and_every_site_named(name):
    n_sites = MODELS[name][1]
    ops = _program_ops(_forward_hlo(name))
    assert ops
    layer = re.compile(rf"/({LAYERS})(?=/|$)")
    assert [o for o in ops if len(layer.findall(o)) != 1] == []
    sites = {c for o in ops for c in o.split("/") if c.startswith("zebra.")}
    assert sites == {f"zebra.z{i}" for i in range(n_sites)}
    # a site's ops lie inside its layer's scope
    assert all(re.search(rf"/({LAYERS})/zebra\.z\d+/", o)
               for o in ops if "/zebra." in o)


def test_scopes_leave_the_compiled_program_unchanged(monkeypatch):
    with_scopes = _forward_hlo("resnet18")
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    without = _forward_hlo("resnet18")
    assert "zebra.z0" in with_scopes and "zebra.z0" not in without
    assert "ENTRY" in _code(with_scopes) and "FileNames" not in _code(without)
    assert _code(with_scopes) == _code(without)


@pytest.mark.parametrize("site,scope", [("ffn_hidden", "zebra.ffn_hidden"),
                                        ("kv_cache", "zebra.kv_cache"),
                                        ("", "zebra")])
def test_token_site_scope(site, scope):
    """LM sites carry their name; an unnamed site is plain ``zebra``, and a
    bare 2-D map (run as a one-sample batch) is not scoped twice."""
    cfg = ZebraConfig(t_obj=0.5, mode="infer", backend="reference")
    for shape in ((2, 16, 256), (16, 256)):
        hlo = jax.jit(lambda x: zebra_site(x, cfg, site=site)[0]).lower(
            jax.ShapeDtypeStruct(shape, jnp.float32)).compile().as_text()
        paths = {tuple(o.split("/")[1:-1]) for o in _program_ops(hlo)}
        assert paths and all(p[:1] == (scope,) for p in paths)
        assert all(p.count(scope) == 1 for p in paths)


def test_degrade_logged_once_and_nothing_per_trace(caplog):
    x = jnp.ones((2, 16, 256), jnp.float32)
    ok = ZebraConfig(t_obj=0.5, mode="infer", backend="reference")
    degraded = ok.replace(mode="train", backend="fused", use_tnet=False)
    with caplog.at_level(logging.DEBUG, logger="repro.engine"):
        zebra_site(x, ok, site="scope_log_ok")
        for _ in range(2):
            _, aux = zebra_site(x, degraded, site="scope_log_degraded")
    assert aux.backend == "reference(not-trainable)"
    records = [r for r in caplog.records if r.name == "repro.engine"]
    assert [(r.levelno, r.getMessage()) for r in records] == [
        (logging.INFO, "zebra_site 'scope_log_degraded': backend 'fused' "
                       "resolved as reference(not-trainable)")]

