"""Readings that set a cell's correctness limits: the program's, and the
control's (the plain reference computed one precision step lower than the
configuration states), on several seeds in one process. Not part of a
benchmark run.

    python3 chipbench/control.py --workload sc2-code --seeds 1,2,3

For each seed the cell's system is built from the seed and serves one
round (one batch for a CNN) of the cell's traffic at the cell's load;
the same numbers the run compares are then read for the program's output
and for the control in its place. One JSON line per seed, then a summary
line with the lower reading (largest over the program's seeds) and the
upper reading (smallest over the control's).
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import bench, generator  # noqa: E402

CONTROL_MODE = {"bfloat16": "fp8", "float32": "bf16"}


def lm_seed(cell, seed: int, check_weights: bool, with_control: bool = True
            ) -> dict:
    import numpy as np
    from repro.serve import ServeEngine
    from repro.serve.bucket import pow2_ceil

    from chipbench.families import lm_serve as drv
    from chipbench.reference import starcoder2 as ref
    c, tr = cell.config, cell.traffic
    cfg = drv.lm_config(c)
    key = drv.seed_key(seed)
    mesh, model, params = drv.build(cfg, key)
    out = {"seed": seed}
    if check_weights:
        emb, layers = ref.weights(key, c)
        p0 = params["run0"]["sub0"]
        same = [bool(np.array_equal(np.asarray(emb), np.asarray(params["embed"])))]
        for name, src in (("wq", p0["attn"]["wq"]), ("wo", p0["attn"]["wo"]),
                          ("w_up", p0["ffn"]["w_up"]),
                          ("w_down", p0["ffn"]["w_down"])):
            same.append(bool(np.array_equal(np.asarray(layers[name]),
                                            np.asarray(src))))
        out["weights_bitwise"] = same
        del emb, layers
    sizes = generator.round_sizes(tr)
    eng = ServeEngine(model, params, mesh, n_slots=int(tr["slots"]),
                      max_cache_len=pow2_ceil(max(p for p, _ in sizes)
                                              + max(m for _, m in sizes)),
                      page_tokens=int(c["served"]["page_tokens"]),
                      validation="off", temperature=0.0, seed=seed % 2 ** 31)
    reqs = drv._Rids().requests(generator.closed_round(
        tr, vocab=c["vocab_size"], seed=seed, round_index=0))
    checked = drv.checked_requests(reqs, tr, seed)
    capture = drv._Capture(eng, [r.rid for r in checked])
    t0 = time.time()
    eng.run(reqs)
    out["round_s"] = time.time() - t0
    got = capture.host(checked)
    rows = drv.ref_rows(tr, eng._prefill_bucket)
    del eng, params, model, capture
    gc.collect()
    t0 = time.time()
    out["program"], lead = drv.readings(c, key, got, rows)
    out["reference_s"] = time.time() - t0
    if with_control:
        ctrl, taken = lm_control(c, key, got,
                                 CONTROL_MODE[c["served"]["dtype"]], rows)
        out["control"], _ = drv.readings(c, key, ctrl, rows, taken)
    out.update(checked=len(got), tokens=sum(r.n for r in got),
               reference_least_lead=lead,
               repeat_rate=float(np.mean([float(np.mean(
                   r.ids[r.P:r.P + r.n] == r.ids[r.P - 1:r.P + r.n - 1]))
                   for r in got])))
    return out


def lm_control(c: dict, key, checked: list, mode: str, rows):
    """The control's output in the program's place, for each checked
    request: the reference's own pass at precision ``mode`` over the same
    sequence, holding what the program's output holds (the prefill's
    last-row logits, the cache rows, and as served tokens the ones its
    own logits put first); and the FFN decisions that pass took, per
    request (``readings``' ``taken``)."""
    import dataclasses

    from chipbench.reference import starcoder2 as ref
    out, taken = list(checked), [None] * len(checked)
    for i, res in ref.forward(c, c["served"]["zebra"], key,
                              [r.seq for r in checked], kmax=rows[0],
                              lbmax=rows[1], mode=mode):
        out[i] = dataclasses.replace(checked[i], logits=res["pre"],
                                     k=res["k"], v=res["v"],
                                     tokens=res["top"])
        taken[i] = res["keep"]
    return out, taken


def cnn_seed(cell, seed: int, check_weights: bool, with_control: bool = True
             ) -> dict:
    import numpy as np

    from chipbench.families import cnn_infer as drv
    from chipbench.families.lm_serve import seed_key
    from chipbench.reference import resnet as ref
    c, tr = cell.config, cell.traffic
    key = seed_key(seed)
    model, zc, init, fwd = drv.program(c)
    variables = init(key)
    batches = drv.images(key, tr, c)
    y = np.asarray(fwd(variables, batches[0]))
    w = ref.weights(key, c)
    out = {"seed": seed}
    if check_weights:
        p = variables["params"]
        out["weights_bitwise"] = [
            bool(np.array_equal(np.asarray(w["stem"]), np.asarray(p["stem"]["w"]))),
            bool(np.array_equal(np.asarray(w["s3b1"]["conv2"]),
                                np.asarray(p["s3b1"]["conv2"]["w"]))),
            bool(np.array_equal(np.asarray(w["fc"]), np.asarray(p["fc"]["w"])))]
    del variables
    items = ref.items(c, c["served"]["zebra"])
    r = np.asarray(ref.logits(w, batches[0], c_items=items), np.float64)

    def numbers(yy):
        return {**drv.logit_numbers(yy, r), "top1_agree": float(np.mean(
            np.argmax(yy, -1) == np.argmax(r, -1)))}
    out["program"] = numbers(y)
    if with_control:
        out["control"] = numbers(np.asarray(ref.logits(
            w, batches[0], c_items=items,
            mode=CONTROL_MODE[c["served"]["dtype"]])))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--check-weights", action="store_true")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="read the control on the first N seeds only")
    a = ap.parse_args(argv)
    b = bench.load_benchmark()
    wl, config, traffic, limits, e2e, layer = bench.resolve(b, a.workload)
    bench.setup_program_path()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    bench.use_compile_cache()
    cell = bench.Cell(workload=wl, config=config, traffic=traffic,
                      limits=limits, seed=0,
                      seconds=0, trace=False, t_start=time.time(),
                      out_dir=bench.ROOT / ".chipbench")
    one = {"lm_serve": lm_seed, "cnn_infer": cnn_seed}[config["family"]]
    rows = []
    for i, s in enumerate(int(x) for x in a.seeds.split(",")):
        row = one(cell, s, a.check_weights and i == 0,
                  i < a.control_seeds)
        rows.append(row)
        print(json.dumps(row), flush=True)
        gc.collect()
    keys = rows[0]["program"].keys()
    print(json.dumps({"workload": a.workload, "seeds": len(rows),
                      "lower": {k: max(r["program"][k] for r in rows) for k in keys},
                      "upper": {k: min(r["control"][k] for r in rows
                                       if "control" in r) for k in keys}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
