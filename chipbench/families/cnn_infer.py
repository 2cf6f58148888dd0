"""Family ``cnn_infer``: CNN inference through the jitted ``ResNet.apply``.

The model is the program's (``repro.models.cnn``), in inference mode
with the configuration's Zebra settings; weights are drawn on the device
in one jitted call from the run's seed. Traffic is a closed loop of
fixed-size batches: ``staged_batches`` seeded synthetic image batches are
put on the device during set-up and cycled, with at most ``inflight``
batches dispatched ahead of the host.

End-to-end metrics (host clock): ``images_per_s`` = images classified in
the window / window seconds (the window ends when its last batch is
ready), and ``setup_s``.

Correctness: after the window, ``check_batches`` timed calls drawn from
the seed are compared with the plain reference (``reference/resnet.py``)
on the same images (``logit_numbers``), each number against its limit.
"""
from __future__ import annotations

import collections
import gc
import shutil
import time

import numpy as np

from chipbench import generator
from chipbench.families.lm_serve import _checks, passes, seed_key

SPAN = "chipbench."


def program(c: dict):
    """(model, ZebraConfig, jitted init, jitted forward) as configured."""
    import jax
    from repro.core import ZebraConfig
    from repro.models.cnn import resnet
    z = c["served"]["zebra"]
    model = getattr(resnet, c["model"])(num_classes=c["num_classes"],
                                        in_hw=c["image_hw"],
                                        width_mult=c["width_mult"])
    want = {"stage_sizes": c["stage_blocks"],
            "stage_channels": c["stage_channels"]}
    got = {"stage_sizes": list(model.stage_sizes),
           "stage_channels": list(model.stage_channels)}
    if got != want:
        raise ValueError(f"the program's {c['model']} is {got}, not {want}")
    zc = ZebraConfig(t_obj=z["t_obj"], block_hw=z["block_hw"],
                     backend=z["backend"], use_tnet=False, mode="infer")
    init = jax.jit(lambda k: model.init(k, zc))
    fwd = jax.jit(lambda v, x: model.apply(v, x, False, zc)[0])
    return model, zc, init, fwd


def images(key, tr: dict, c: dict):
    import jax
    import jax.numpy as jnp
    shape = (int(tr["staged_batches"]), int(tr["batch"]), c["in_channels"],
             c["image_hw"], c["image_hw"])

    dt = jnp.dtype(c["served"]["zebra"]["map_dtype"])

    @jax.jit
    def make(k):
        return jax.random.normal(k, shape, jnp.float32).astype(dt)
    return list(make(jax.random.fold_in(key, 1)))


def _loop(fwd, variables, batches, seconds, inflight):
    """Closed loop over the staged batches for ``seconds``; returns
    (outputs, window start, window end)."""
    outs, pending = [], collections.deque()
    t0 = time.time()
    i = 0
    while True:
        y = fwd(variables, batches[i % len(batches)])
        outs.append(y)
        pending.append(y)
        i += 1
        if len(pending) > inflight:
            pending.popleft().block_until_ready()
        if time.time() - t0 >= seconds:
            break
    for y in pending:
        y.block_until_ready()
    return outs, t0, time.time()


def logit_numbers(y, r) -> dict:
    """A batch's answers against the reference's logits ``r``: the widest
    gap by which the reference's logit of the program's top class lies
    below the reference's best, and the largest logit error over the RMS
    of the reference's logits."""
    y = np.asarray(y, np.float64)
    top = np.argmax(y, axis=-1)
    rms = float(np.sqrt(np.mean(r ** 2)))
    return {"top1_logit_gap": float(np.max(r.max(-1)
                                           - r[np.arange(len(r)), top])),
            "logit_err_rms": float(np.max(np.abs(y - r))) / rms}


def run(cell) -> dict:
    import jax

    from chipbench.bench import CompileCounter, memory_peak_bytes
    from chipbench.reference import resnet as ref

    c, tr = cell.config, cell.traffic
    counter = CompileCounter()
    key = seed_key(cell.seed)
    t_w = time.time()
    model, zc, init, fwd = program(c)
    variables = init(key)
    batches = images(key, tr, c)
    jax.block_until_ready((variables, batches))
    t_params = time.time() - t_w
    t_w = time.time()
    for b in batches[:2]:               # the one shape the window uses
        fwd(variables, b).block_until_ready()
    t_warm = time.time() - t_w

    counter.active = True
    t_begin = time.time()
    setup_s = t_begin - cell.t_start
    rec, breakdown, counters = None, {}, {}
    inflight = int(tr["inflight"])
    if cell.trace:
        cell.out_dir.mkdir(parents=True, exist_ok=True)
        tdir = cell.out_dir / f"trace_{cell.name}_{cell.seed}"
        jax.profiler.start_trace(str(tdir))
        with jax.profiler.TraceAnnotation(SPAN + "window"):
            outs, t0, t1 = _loop(fwd, variables, batches,
                                 min(cell.seconds, float(tr["trace_seconds"])),
                                 inflight)
        jax.profiler.stop_trace()
    else:
        outs, t0, t1 = _loop(fwd, variables, batches, cell.seconds, inflight)
    counter.active = False
    n_img = len(outs) * int(tr["batch"])
    e2e = {"images_per_s": n_img / (t1 - t0), "setup_s": setup_s}
    mem = memory_peak_bytes(int(cell.workload["chips"]))
    if cell.trace:
        from chipbench.metrics.lib import trace as trl
        rec = trl.extract(trl.find_xplane(str(tdir)))
        shutil.rmtree(tdir, ignore_errors=True)
        trl.save(rec, f"{tdir}.json.gz")
        counters = {"window_s": t1 - t0, "images": n_img}
        breakdown = {"device_ops": trl.top(trl.op_seconds(rec)),
                     "idle_gaps": trl.top(trl.idle_by_span(rec))}

    # -- correctness on a sample of the timed calls
    pick = generator.staged_sample(len(outs), cell.seed,
                                   int(tr["check_batches"]))
    got = [(i, np.asarray(outs[i])) for i in pick]
    imgs = [batches[i % len(batches)] for i in pick]
    del outs, variables, model, fwd, init
    gc.collect()
    t_r = time.time()
    w = ref.weights(key, c)
    items = ref.items(c, c["served"]["zebra"])
    numbers = {"top1_logit_gap": 0.0, "logit_err_rms": 0.0}
    for (i, y), x in zip(got, imgs):
        r = np.asarray(ref.logits(w, x, c_items=items), np.float64)
        for k, v in logit_numbers(y, r).items():
            numbers[k] = max(numbers[k], v)
    t_ref = time.time() - t_r
    checks = _checks(numbers, cell.limits)
    correct = passes(checks)
    notes = {"params_s": round(t_params, 3), "warmup_s": round(t_warm, 3),
             "batches": n_img // int(tr["batch"]),
             "compile_requests_in_window": counter.requests,
             "compiles_in_window": counter.compiles,
             "cache_load_s": round(counter.load_s, 3),
             "compile_s": round(counter.compile_s, 3),
             "reference_s": round(t_ref, 3)}
    return {"correct": correct, "attempted": n_img // int(tr["batch"]),
            "failed": 0, "e2e": e2e, "checks": checks, "trace": rec,
            "counters": counters, "breakdown": breakdown,
            "memory_peak_bytes": mem, "notes": notes}
