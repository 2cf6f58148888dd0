"""Compile the cells' programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 chipbench/aot.py [--cells sc2-code,r18-infer]

For each serve cell: the prefill at every prompt bucket its traffic
uses and the slotted decode at the top batch and cache buckets; for the
CNN cell, the batch forward. Prints each program's device memory (the
compiler's own analysis) against the chip's 16 GB, so a program that
does not fit fails here rather than on the chip. Not part of a run.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import bench, generator  # noqa: E402

HBM = 16e9


def _report(name, compiled) -> float:
    m = compiled.memory_analysis()
    tot = (m.argument_size_in_bytes + m.output_size_in_bytes
           + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, "
          f"outputs {m.output_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB, total {tot / 1e9:.3f} GB "
          f"of {HBM / 1e9:.0f}", flush=True)
    return tot


def lm_programs(cell, dev, mesh) -> float:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.launch.steps import make_decode_slotted, make_prefill
    from repro.models.lm import LM
    from repro.serve.bucket import pow2_ceil, pow2_floor

    from chipbench.families.lm_serve import lm_config
    c, tr = cell.config, cell.traffic
    cfg = lm_config(c)
    model = LM(cfg)
    sh = SingleDeviceSharding(dev)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
    params = jax.tree_util.tree_map(
        sds, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    sizes = generator.round_sizes(tr)
    worst = 0.0
    prefill = jax.jit(make_prefill(model, mesh))
    for pb in sorted({pow2_floor(p) for p, _ in sizes}):
        tok = jax.ShapeDtypeStruct((1, pb), jnp.int32, sharding=sh)
        worst = max(worst, _report(f"{cell.name} prefill {pb}",
                                   prefill.lower(params, tok).compile()))
    top_c = pow2_ceil(max(p for p, _ in sizes) + max(m for _, m in sizes))
    B = int(tr["slots"])
    caches = jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda: model.init_cache(B, top_c)))
    decode = jax.jit(make_decode_slotted(model, mesh, 0.0),
                     donate_argnums=(2,))
    args = (params, jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=sh),
            (caches, None), jax.ShapeDtypeStruct((B,), jnp.int32, sharding=sh),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sh))
    worst = max(worst, _report(f"{cell.name} decode ({B}, {top_c})",
                               decode.lower(*args).compile()))
    return worst


def cnn_programs(cell, dev, mesh) -> float:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from chipbench.families.cnn_infer import program
    c, tr = cell.config, cell.traffic
    model, zc, init, fwd = program(c)
    sh = SingleDeviceSharding(dev)
    v = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        jax.eval_shape(init, jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((int(tr["batch"]), c["in_channels"],
                              c["image_hw"], c["image_hw"]),
                             jnp.dtype(c["served"]["zebra"]["map_dtype"]),
                             sharding=sh)
    return _report(f"{cell.name} forward", fwd.lower(v, x).compile())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cells", default="")
    a = ap.parse_args(argv)
    spec = bench.load_benchmark()
    bench.setup_program_path()
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # the program picks its Pallas TPU forms by asking for the backend
    jax.default_backend = lambda: "tpu"
    dev = topo.devices[0]
    mesh = Mesh(np.array([dev]).reshape(1, 1), ("data", "model"))
    cells = a.cells.split(",") if a.cells else bench.list_cells(spec)
    bad = []
    for name in cells:
        wl, config, traffic, limits, e2e, layer = bench.resolve(spec, name)
        cell = bench.Cell(workload=wl, config=config, traffic=traffic,
                          limits=limits, seed=0, seconds=0, trace=False,
                          t_start=0.0, out_dir=bench.ROOT)
        one = {"lm_serve": lm_programs, "cnn_infer": cnn_programs}[
            config["family"]]
        if one(cell, dev, mesh) > HBM:
            bad.append(name)
    print("does not fit:" if bad else "every program fits", *bad)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
