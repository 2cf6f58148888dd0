"""Where kernels run and where compiled programs are cached: both follow
the environment, never a caller's option."""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.kernels import platform

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_pallas_interprets_only_off_a_tpu(monkeypatch):
    assert platform.pallas_interpret() == (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert platform.pallas_interpret() is False
    assert platform.tpu_forms(8, 128) and platform.tpu_forms(16, 256)
    assert not platform.tpu_forms(4, 4)          # paper's NCHW blocks
    assert not platform.tpu_forms(8, 192)        # lane-misaligned channels
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert platform.pallas_interpret() is True
    assert not platform.tpu_forms(8, 128)


@pytest.mark.parametrize("platform_name,label", [
    ("cpu", "stream"), ("tpu", "reference(narrow-blocks)")])
def test_narrow_blocks_leave_the_kernels_only_on_a_tpu(monkeypatch,
                                                       platform_name, label):
    """4x4 NCHW blocks run the stream backend's kernels where they are
    interpreted; on a TPU, which no Pallas form of those blocks compiles
    for, the site resolves to the reference masked map and claims no
    stream bytes."""
    import jax.numpy as jnp

    from repro.core import ZebraConfig
    from repro.core.engine import zebra_site
    monkeypatch.setattr(jax, "default_backend", lambda: platform_name)
    cfg = ZebraConfig(t_obj=0.5, use_tnet=False, backend="stream",
                      mode="infer")
    x = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(0), (2, 3, 8, 8)))
    y, aux = zebra_site(x, cfg, site="z0", layout="nchw")
    ref, _ = zebra_site(x, cfg.replace(backend="reference"), layout="nchw")
    assert aux.backend == label
    assert jnp.array_equal(y, ref)
    assert (int(aux.measured_bytes) > 0) == (platform_name == "cpu")


@pytest.mark.parametrize("placed", [False, True])
def test_compile_cache_directory(tmp_path, placed):
    """Unset, the cache lands at ``<checkout>/.jax_cache``; set, the
    directory JAX_COMPILATION_CACHE_DIR names is left to JAX."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = str(REPO / ".jax_cache")
    if placed:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    code = ("import jax\n"
            "from repro.launch.cache import use_compile_cache\n"
            "print(use_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.split() == [want, want]
