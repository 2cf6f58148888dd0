"""Chip smoke: the serve and train paths once on a TPU, through the entry
points a user calls, at published widths with seeded random weights.

    python chip_smoke.py              # serve + cnn phases, one chip
    python chip_smoke.py --chips 4    # compressed collectives, 1x4 mesh only

Phases (one process, no children; any failed check exits non-zero):

* serve — starcoder2-15b at its published widths, depth cut to 8 of 40
  layers, bf16, ``fused`` backend: 8 requests over 4 slots through the
  continuous launcher path (``launch.serve.serve_continuous``), then
  prefill + decode logits of one request against the ``reference``
  backend on the same params.
* cnn — ResNet-18 at the paper's Tiny-ImageNet shape (200 classes,
  64x64, width 1.0), batch 128 of seeded synthetic images: ``stream``
  inference against ``reference``, then 3 constant-threshold training
  steps (``train.cnn_trainer``) on ``stream`` against ``reference``. On
  a TPU the 4x4 NCHW blocks fill no vreg tile, so every site resolves to
  ``reference(narrow-blocks)`` and moves no stream; the phase checks
  that label and the zero bytes it claims.
* collectives (``--chips 4``) — ``zebra_all_gather`` and
  ``zebra_psum_stream`` on a starcoder2-width shard per device against
  ``lax.all_gather`` / ``lax.psum``.

Times printed here are a single smoke run, not a benchmark. The last
line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SERVE_T_OBJ = 1.6       # puts ~2/3 of the random-weight FFN hidden blocks
                        # under threshold (GELU of N(0, 1/4) pre-activations)
SERVE_ZF = (0.5, 0.8)   # required prefill ffn_hidden zero-block fraction
DECODE_STEPS = 3
# Inference logits must equal the reference backend's bit for bit: the
# kernel backends' contract is bitwise-identical masked maps, and the fused
# FFN is bitwise equal to the reference one in bf16 (README, parity).
CNN_T_OBJ = 1.2         # ~2/3 zero blocks at the ResNet-18 sites
CNN_BATCH = 128
# CNN logits and training losses must equal the reference backend's bit
# for bit too: on a TPU every ResNet-18 site has 4x4 (or smaller) blocks
# and resolves to the reference path, so both programs are the same.
STREAMING = ("stream", "fused")     # labels of sites that move a stream


def _t() -> float:
    return time.perf_counter()


def _rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED {what}")
    print(f"  ok: {what}", flush=True)


def _labels(rec: dict) -> dict:
    return {site: sorted(v) for site, v in sorted(rec.items())}


@contextlib.contextmanager
def resolutions():
    """The backend every site traced inside the block resolved to, as the
    site engine resolves it. A function traced before the block records
    nothing."""
    from repro.core import engine
    seen: dict[str, set[str]] = {}
    resolved = engine._log_resolution

    def record(site, requested, label, degraded):
        seen.setdefault(site, set()).add(label)
        resolved(site, requested, label, degraded)

    engine._log_resolution = record
    try:
        yield seen
    finally:
        engine._log_resolution = resolved


def _check_cnn_sites(labels) -> None:
    """A site either keeps the stream backend or, on a TPU, resolves to
    the reference path because its blocks are narrower than a vreg tile;
    any other degrade fails the phase."""
    other = sorted({lb for lb in labels
                    if lb != "reference(narrow-blocks)"
                    and lb.startswith("reference")})
    _check(not other, f"no site degraded for another reason ({other})")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _kv_zero_blocks(caches, S: int, bs: int, bc: int) -> tuple[int, int]:
    """(all-zero blocks, blocks) over the first S positions of every K/V
    cache leaf ``(..., T, Hkv, hd)`` — the kv_cache site's share of the
    prefill aux, which also counts ffn_hidden."""
    import jax
    zero = total = 0
    for leaf in jax.tree_util.tree_leaves(caches):
        x = np.asarray(leaf[..., :S, :, :].astype("float32"))
        d = x.shape[-2] * x.shape[-1]
        blocks = np.abs(x.reshape(-1, S // bs, bs, d // bc, bc)).max(axis=(2, 4))
        zero += int((blocks == 0).sum())
        total += blocks.size
    return zero, total


def serve_phase(seed: int, base=None) -> None:
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.launch import serve as launch
    from repro.launch.steps import make_decode_step, make_prefill
    from repro.models.lm import LM
    from repro.serve import synthetic_trace
    from repro.serve.bucket import pow2_floor

    args = launch.parse_args([
        "--arch", "starcoder2-15b", "--backend", "fused",
        "--t-obj", str(SERVE_T_OBJ), "--requests", "8", "--slots", "4",
        "--prompt-len", "512", "--gen", "32", "--seed", str(seed)])
    base = base or configs.get("starcoder2-15b").replace(n_layers=8)
    cfg = launch.serve_config(args, base.replace(zebra_tnet=False))
    print(f"[serve] {cfg.name}, {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"d_ff {cfg.d_ff}, {cfg.zebra_backend} backend", flush=True)
    t0 = _t()
    mesh, model, params = launch.build(args, cfg)
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    print(f"  params: {n_bytes / 1e9:.2f} GB in {_t() - t0:.1f} s", flush=True)

    trace = synthetic_trace(8, vocab=cfg.vocab, seed=seed, prompt_lo=128,
                            prompt_hi=512, gen_lo=16, gen_hi=32)
    t0 = _t()
    with resolutions() as served:
        rep = launch.serve_continuous(args, cfg, mesh, model, params,
                                      trace=trace)
    print(f"  continuous run incl. compiles: {_t() - t0:.1f} s "
          "(chip smoke, not a benchmark)", flush=True)
    print(f"  resolved backends (prefill + decode): {_labels(served)}")
    _check(rep["n_requests"] == 8 and rep["n_rejected"] == 0
           and rep["n_shed"] == 0, "all 8 requests served")
    _check(rep["tokens"] == sum(r.max_new for r in trace),
           f"{rep['tokens']} tokens generated")
    _check(rep["pages_recovered"] == 0, "no KV page recovered dense")
    _check(rep["kv_pages"] > 0 and abs(rep["reconcile_max_delta_bytes"]) < 1.0,
           f"KV reconcile over {rep['kv_pages']} pages within 1 B of Eq. 2/3")

    # one request's prefill + decode logits, fused against reference
    r = trace[0]
    S = pow2_floor(r.prompt_len)
    prompt = jnp.asarray(r.prompt[:S], jnp.int32)[None]
    feed = np.random.default_rng(seed).integers(1, cfg.vocab,
                                                size=DECODE_STEPS)
    ref_model = LM(cfg.replace(zebra_backend="reference"))
    out = {}
    for name, m in (("fused", model), ("reference", ref_model)):
        t0 = _t()
        with resolutions() as rec:
            logits, state, aux = launch.model_prefill_pad(
                jax.jit(make_prefill(m, mesh)), params, prompt,
                S + DECODE_STEPS)
            logits = np.asarray(logits.astype(jnp.float32))
        kv_zero, kv_blocks = _kv_zero_blocks(state[0], S, cfg.zebra_block_seq,
                                             cfg.zebra_block_ch)
        ffn_zf = ((float(aux.zf_blocks) - kv_zero)
                  / (float(aux.n_blocks) - kv_blocks))
        decode = jax.jit(make_decode_step(m, mesh))
        steps = []
        for i in range(DECODE_STEPS):
            lg, state = decode(params, jnp.asarray([[feed[i]]], jnp.int32),
                               state, jnp.int32(S + i))
            steps.append(np.asarray(lg.astype(jnp.float32)))
        out[name] = (logits, steps)
        print(f"  {name}: prefill S={S} + {DECODE_STEPS} decode steps in "
              f"{_t() - t0:.1f} s incl. compile; prefill sites "
              f"{_labels(rec)}; ffn_hidden zero-block fraction "
              f"{ffn_zf:.4f}", flush=True)
        if name == "fused":
            bad = [lb for lb in rec.get("ffn_hidden", ())
                   if lb.startswith("reference")]
            _check("ffn_hidden" in rec and not bad,
                   f"every prefill ffn_hidden site on {sorted(rec.get('ffn_hidden', ()))}")
            _check(SERVE_ZF[0] <= ffn_zf <= SERVE_ZF[1],
                   f"prefill ffn_hidden zero-block fraction {ffn_zf:.4f} in "
                   f"{SERVE_ZF}")
    (lf, df), (lr, dr) = out["fused"], out["reference"]
    for what, a, b in [("prefill", lf, lr)] + [
            (f"decode step {i}", a, b) for i, (a, b) in enumerate(zip(df, dr))]:
        _check(np.isfinite(a).all() and np.array_equal(a, b),
               f"{what} logits finite and bitwise equal to reference "
               f"(rel-L2 {_rel_l2(a, b):.2e})")


# ---------------------------------------------------------------------------
# cnn
# ---------------------------------------------------------------------------

def cnn_phase(seed: int, batch: int = CNN_BATCH, hw: int = 64) -> None:
    import jax

    from repro.core import MapSpec, ZebraConfig, stored_bits
    from repro.data.synthetic import SYN_TINYIMAGENET, image_batch
    from repro.optim import constant, sgd
    from repro.train.cnn_trainer import CNNTrainConfig, CNNTrainer

    print(f"[cnn] resnet18, 200 classes, {hw}x{hw}, batch {batch}",
          flush=True)
    ds = dataclasses.replace(SYN_TINYIMAGENET, hw=hw, seed=seed)
    zc = ZebraConfig(t_obj=CNN_T_OBJ, use_tnet=False)
    trainers = {b: CNNTrainer(CNNTrainConfig(
        model="resnet18", dataset=ds, batch=batch,
        zebra=zc.replace(backend=b)), sgd(constant(0.01)))
        for b in ("stream", "reference")}
    model = trainers["stream"].model
    state0 = trainers["stream"].init_state(jax.random.PRNGKey(seed))
    images, _ = image_batch(ds, batch, 0)

    out = {}
    for b in trainers:
        zi = zc.replace(backend=b, mode="infer")
        t0 = _t()
        logits, _, auxes = jax.jit(
            lambda v, x, zi=zi: model.apply(v, x, False, zi))(
                state0["variables"], images)
        out[b] = (np.asarray(logits), auxes)
        print(f"  infer {b}: {_t() - t0:.1f} s incl. compile; sites "
              f"{sorted({a.backend for a in auxes})}", flush=True)
    (ls, aux_s), (lr, _) = out["stream"], out["reference"]
    _check_cnn_sites([a.backend for a in aux_s])
    _check(np.isfinite(ls).all() and np.array_equal(ls, lr),
           f"stream logits finite and bitwise equal to reference "
           f"(rel-L2 {_rel_l2(ls, lr):.2e})")
    # per-site bytes: the Eq. 2/3 stream length at the measured zero
    # fraction where a stream moves (slack = index-byte padding, < 1 B,
    # plus the f32 rounding of that fraction over the whole map), and
    # none where the site kept the dense map
    over, zfs, n_streaming = [], [], 0
    for i, (a, spec) in enumerate(zip(aux_s, model.map_specs(hw, zc))):
        bspec = MapSpec(c=batch * spec.c, h=spec.h, w=spec.w, bits=32,
                        block=spec.block)
        zfs.append(float(a["zero_frac"]))
        streaming = a.backend in STREAMING
        n_streaming += streaming
        want = stored_bits(bspec, zfs[-1]) / 8.0 if streaming else 0.0
        slack = 1.0 + bspec.map_bits / 8.0 * 2.0 ** -22 if streaming else 0.0
        if abs(float(a["measured_bytes"]) - want) > slack:
            over.append(i)
    _check(not over, f"{len(aux_s)} sites' measured bytes: Eq. 2/3 at the "
           f"{n_streaming} that move a stream, 0 at the rest (sites off: "
           f"{over})")
    print(f"  site zero-block fractions {np.round(zfs, 4).tolist()} "
          f"(mean {np.mean(zfs):.4f})", flush=True)

    losses = {}
    for b, tr in trainers.items():
        t0 = _t()
        with resolutions() as rec:
            _, hist = tr.train(steps=3, log_every=1, state=state0)
        losses[b] = [h["loss"] for h in hist]
        labels = set().union(*rec.values())
        print(f"  train {b}: 3 steps in {_t() - t0:.1f} s incl. compile; "
              f"losses {losses[b]}; sites {sorted(labels)}", flush=True)
        if b == "stream":
            _check_cnn_sites(labels)
    for i, (a, b) in enumerate(zip(losses["stream"], losses["reference"])):
        _check(np.isfinite(a) and a == b,
               f"step {i + 1} loss {a:.6f} bitwise equal to reference")


# ---------------------------------------------------------------------------
# collectives (four chips)
# ---------------------------------------------------------------------------

def collectives_phase(seed: int) -> None:
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed import collectives as coll
    from repro.launch.mesh import make_host_mesh

    n = len(jax.devices())
    _check(n == 4, f"{n} devices for a 1x4 mesh")
    mesh = make_host_mesh(model=4)
    M, K, BS, BC = 2048, 6144, 8, 128
    nm, nk = M // BS, K // BC
    print(f"[collectives] 1x4 ('data','model') mesh, ({M}, {K}) bf16 shard "
          "per device", flush=True)
    rng = np.random.default_rng(seed)
    keep = rng.random((n, nm, nk)) > 0.64
    vals = rng.integers(-8, 9, size=(n, M, K), dtype=np.int8)
    shards = vals * np.repeat(np.repeat(keep, BS, 1), BC, 2)
    live = [int((np.abs(s).reshape(nm, BS, nk, BC).max(axis=(1, 3)) > 0).sum())
            for s in shards]
    union = int((np.abs(shards).reshape(n, nm, BS, nk, BC).max(axis=(2, 4))
                 > 0).any(axis=0).sum())
    print(f"  zero-block fraction {1 - sum(live) / (n * nm * nk):.4f}")
    X = jax.device_put(jnp.asarray(shards.reshape(n * M, K), jnp.bfloat16),
                       NamedSharding(mesh, P("model", None)))

    def stream(n_live: int) -> int:   # Eq. 2/3 (core.engine.stream_bytes)
        return n_live * BS * BC * 2 + (nm * nk + 7) // 8

    sm = functools.partial(coll.shard_map_compat, mesh=mesh,
                           in_specs=(P("model", None),))

    def links(link):      # per-link bytes summed over the axis's links
        return lax.psum(link.moved, "model"), lax.psum(link.dense, "model")

    def all_gather(x):
        y, link = coll.zebra_all_gather(x, "model", bs=BS, bc=BC, tiled=True)
        return (y, *links(link))

    def psum(x):
        y, _, link = coll.zebra_psum_stream(x, "model", bs=BS, bc=BC)
        return (y, *links(link))

    cases = {
        "all_gather": (all_gather,
                       lambda x: lax.all_gather(x, "model", axis=0, tiled=True),
                       P(), (n - 1) * sum(stream(lv) for lv in live)),
        "psum": (psum, lambda x: lax.psum(x, "model"), P("model", None),
                 n * (n - 1) * stream(union)),
    }
    for name, (comp, dense, out_spec, pred) in cases.items():
        t0 = _t()
        y_c, moved, dense_b = jax.jit(sm(comp, out_specs=(out_spec, P(), P())))(X)
        y_d = jax.jit(sm(dense, out_specs=out_spec))(X)
        jax.block_until_ready((y_c, y_d))
        print(f"  {name}: {_t() - t0:.1f} s incl. compile (chip smoke, not a "
              f"benchmark); output on {len(y_c.sharding.device_set)} devices "
              f"(dense {len(y_d.sharding.device_set)})", flush=True)
        _check(len(y_c.sharding.device_set) == n,
               f"{name} output spans all {n} devices")
        _check(np.array_equal(np.asarray(y_c.astype(jnp.float32)),
                              np.asarray(y_d.astype(jnp.float32))),
               f"{name} bitwise equal to its lax collective")
        _check(int(moved) == pred and int(moved) < int(dense_b),
               f"{name} ici_bytes {int(moved)} == Eq. 2/3 {pred} "
               f"(dense {int(dense_b)})")


# ---------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the compressed-collectives phase on a "
                         "1x4 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); this check runs only on a TPU",
              file=sys.stderr)
        return 2

    from repro.launch.cache import use_compile_cache
    print(f"device: {devices[0].device_kind} x {len(devices)}; compile "
          f"cache {use_compile_cache()}", flush=True)
    t0 = _t()
    if args.chips == 4:
        collectives_phase(args.seed)
    else:
        serve_phase(args.seed)
        cnn_phase(args.seed)
    print(f"total {_t() - t0:.1f} s (chip smoke, not a benchmark)",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
