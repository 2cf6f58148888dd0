"""distributed/sharding.py + ctx.hint — the previously untested rule layer.

All mesh-dependent assertions run in ONE subprocess on 8 forced host
devices (the XLA flag must not leak into the main test process), mesh
(4 data x 2 model): ``spec_for``'s kv-axis fallback, ``_axis_ok``'s
non-divisible degrade, the pure-DP profile rewriting "model" -> None,
``batch_spec``'s axis dropping, and ``ctx.hint`` dropping unknown /
non-dividing axes under jit.
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.distributed import sharding as sh
from repro.distributed.ctx import sharding_hints, hint
from repro.launch.mesh import _make_mesh
from repro.models.lm.config import LMConfig

mesh = _make_mesh((4, 2), ("data", "model"))
out = {}
tp = LMConfig(sharding_profile="tp")            # n_kv_heads=4, model=2 ok
kv_bad = tp.replace(n_kv_heads=3)               # 3 % 2 != 0 -> kv fallback
dp = LMConfig(sharding_profile="dp")

def spec(names, shape, cfg):
    return [list(a) if isinstance(a, tuple) else a
            for a in sh.spec_for(names, shape, cfg, mesh)]

# --- kv-axis fallback: wk shards heads over "model" only when divisible ---
out["wk_tp"] = spec(("layers", "attn", "wk"), (512, 4, 128), tp)
out["wk_kv_bad"] = spec(("layers", "attn", "wk"), (512, 3, 128), kv_bad)

# --- _axis_ok non-divisible degrade: d_ff=100 not divisible by model=2 ---
out["w_up_ok"] = spec(("layers", "ffn", "w_up"), (512, 2048), tp)
out["w_up_bad"] = spec(("layers", "ffn", "w_up"), (512, 99), tp)
# data axis (4) must divide the fan-in too
out["w_up_bad_data"] = spec(("layers", "ffn", "w_up"), (510, 2048), tp)

# --- pure-DP profile: every "model" rewritten to None ---
out["w_up_dp"] = spec(("layers", "ffn", "w_up"), (512, 2048), dp)
out["embed_dp"] = spec(("embed",), (32000, 512), dp)
out["embed_tp"] = spec(("embed",), (32000, 512), tp)

# --- run-stacked leaves get the leading None prepended ---
out["wq_stacked"] = spec(("layers", "attn", "wq"), (8, 512, 8, 64), tp)

# --- unknown leaves replicate ---
out["unknown"] = spec(("whatever", "mystery_w"), (16, 16), tp)

# --- batch_spec axis dropping ---
out["bs_8"] = [list(a) if isinstance(a, tuple) else a
               for a in sh.batch_spec(mesh, 3, batch=8)]
out["bs_1"] = [list(a) if isinstance(a, tuple) else a
               for a in sh.batch_spec(mesh, 3, batch=1)]
out["bs_dp"] = [list(a) if isinstance(a, tuple) else a
                for a in sh.batch_spec(mesh, 3, batch=8, cfg=dp)]

# --- ctx.hint: unknown and non-dividing axes drop under jit ---
def spec_of(x):
    s = getattr(x, "sharding", None)
    return getattr(s, "spec", None)

with sharding_hints(mesh):
    ok = jax.jit(lambda x: hint(x, "data", "model"))(
        jnp.zeros((8, 256)))
    bad_axis = jax.jit(lambda x: hint(x, "data", "nonexistent"))(
        jnp.zeros((8, 256)))
    bad_div = jax.jit(lambda x: hint(x, "data", "model"))(
        jnp.zeros((8, 255)))                      # 255 % 2 != 0
out["hint_ok"] = str(spec_of(ok))
out["hint_unknown_axis"] = str(spec_of(bad_axis))
out["hint_non_dividing"] = str(spec_of(bad_div))
no_ctx = jax.jit(lambda x: hint(x, "data", "model"))(jnp.zeros((8, 256)))
out["hint_no_ctx"] = str(spec_of(no_ctx))

print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_sharding_rules_on_8_devices():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])

    # kv fallback: 4 kv heads shard over model; 3 kv heads replicate
    assert out["wk_tp"] == ["data", "model", None]
    assert out["wk_kv_bad"] == ["data", None, None]

    # non-divisible dims drop just their axis, keeping the rest
    assert out["w_up_ok"] == ["data", "model"]
    assert out["w_up_bad"] == ["data", None]
    assert out["w_up_bad_data"] == [None, "model"]

    # pure-DP rewrites "model" -> None everywhere
    assert out["w_up_dp"] == ["data", None]
    assert out["embed_dp"] == [None, None]
    assert out["embed_tp"] == ["model", None]

    # run-stacked leaves: rules fire on trailing dims, leading None
    assert out["wq_stacked"] == [None, "data", "model", None]
    assert out["unknown"] == []

    # batch_spec: full DP when divisible, all dropped at batch=1;
    # pure-DP adds "model" to the batch axes (PartitionSpec normalises a
    # one-axis tuple to the bare axis name)
    assert out["bs_8"] == ["data", None, None]
    assert out["bs_1"] == [None, None, None]
    assert out["bs_dp"] == [["data", "model"], None, None]

    # hint: valid constraint applies; unknown/non-dividing axes drop to
    # None on that dim; no context leaves the default sharding
    assert "data" in out["hint_ok"] and "model" in out["hint_ok"]
    assert "nonexistent" not in out["hint_unknown_axis"]
    assert "model" not in out["hint_non_dividing"]
    assert "data" in out["hint_non_dividing"]
    assert "data" not in out["hint_no_ctx"]
