"""Serving launcher — a thin CLI over two paths:

* one-shot batch (default): prefill a batch of prompts, then decode with
  the sharded KV cache (+ Zebra KV-cache block compression accounting);
* continuous batching (``--requests N``): serve a synthetic
  heavy-traffic trace through ``repro.serve.ServeEngine`` — request
  admission, slotted decode across in-flight requests at different
  positions, and a paged pool of compressed KV payload slabs.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --reduced \
        --batch 4 --prompt-len 64 --gen 32
    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --reduced \
        --requests 16 --slots 8 --gen 24 --validate structural
"""
from __future__ import annotations

import argparse
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import configs
from ..data import LMDatasetConfig, lm_batch
from ..distributed import sharding as shd
from ..models.lm import LM
from ..serve.bucket import pow2_bucket, pow2_ceil
from .cache import use_compile_cache
from .mesh import make_host_mesh
from .steps import _next_token, make_generate, make_prefill


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--t-obj", type=float, default=0.1)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy argmax; > 0 samples from the softmax "
                         "at this temperature (seeded by --seed)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--use-kernel", action="store_true",
                    help="legacy alias for --backend stream (compressed "
                         "activation transport + measured-bytes accounting)")
    ap.add_argument("--backend", default="",
                    choices=["", "reference", "pallas", "stream", "fused"],
                    help="Zebra site-engine backend for every activation "
                         "site (core.engine); stream/fused also transport "
                         "the prefill->decode KV caches compressed")
    ap.add_argument("--validate", default="off",
                    choices=["off", "structural", "checksum"],
                    help="stream-integrity level at every ingest boundary "
                         "(compress.integrity): the engine's in-graph "
                         "producer->consumer checks, host-side validation "
                         "of the prefill->decode cache handoff, and the "
                         "serve pool's per-page ingest check with dense "
                         "fallback")
    ap.add_argument("--requests", type=int, default=0,
                    help="continuous-batching mode: serve a synthetic "
                         "trace of N requests (repro.serve.ServeEngine) "
                         "instead of the one-shot batch path")
    ap.add_argument("--slots", type=int, default=4,
                    help="in-flight request lanes (continuous mode)")
    ap.add_argument("--page-tokens", type=int, default=16,
                    help="cache positions per compressed KV page")
    ap.add_argument("--preempt-after", type=int, default=0,
                    help="evict a lane to the compressed pool after this "
                         "many consecutive steps while requests wait "
                         "(0 = never)")
    ap.add_argument("--deadline-ticks", type=int, default=0,
                    help="per-request TTL in engine ticks (continuous "
                         "mode): a request that cannot finish by "
                         "arrival + TTL given the slot clock is shed at "
                         "admission, and a lane past its TTL is "
                         "cancelled mid-flight (0 = no deadlines)")
    ap.add_argument("--queue-bound", type=int, default=0,
                    help="bounded pending queue (continuous mode): "
                         "arrived waiters beyond this count are shed, "
                         "newest fresh arrivals first (0 = unbounded)")
    ap.add_argument("--supervise", action="store_true",
                    help="run the continuous engine loop under the "
                         "crash-recoverable supervisor (per-tick "
                         "snapshots + classified restore/backoff)")
    return ap.parse_args(argv)


def serve_config(args: argparse.Namespace, base=None):
    """The served LMConfig: ``base`` (default: ``--arch``, ``--reduced``)
    in bf16 with the KV-cache site added and the CLI's Zebra options."""
    cfg = base or (configs.reduced(args.arch) if args.reduced
                   else configs.get(args.arch))
    return cfg.replace(param_dtype="bfloat16",
                       zebra_sites=tuple(cfg.zebra_sites) + ("kv_cache",),
                       zebra_t_obj=args.t_obj,
                       zebra_backend=args.backend or (
                           "stream" if args.use_kernel else ""),
                       zebra_validation=args.validate)


def build(args: argparse.Namespace, cfg):
    """Mesh, model and sharded random params (``PRNGKey(0)``) for ``cfg``."""
    mesh = make_host_mesh(model=args.model_parallel)
    model = LM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    pshard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        shd.param_specs(params, cfg, mesh), is_leaf=lambda x: isinstance(x, P))
    return mesh, model, jax.device_put(params, pshard)


def main() -> None:
    args = parse_args()
    use_compile_cache()
    cfg = serve_config(args)
    backend = cfg.zebra_backend
    mesh, model, params = build(args, cfg)

    if args.requests:
        serve_continuous(args, cfg, mesh, model, params)
        return

    key = jax.random.PRNGKey(args.seed)
    prefill = jax.jit(make_prefill(model, mesh), static_argnames=())
    # whole-generation lax.scan: ONE dispatch for gen-1 tokens (steps.py);
    # length-0 scan at --gen 1 costs nothing. With a compressed handoff the
    # state arrives in payload form, whose buffers can't back the dense
    # outputs — donating them would only warn.
    donate = () if backend in ("stream", "fused") else (2,)
    generate = jax.jit(make_generate(model, mesh, max(args.gen - 1, 0),
                                     args.temperature),
                       donate_argnums=donate)

    ds = LMDatasetConfig(vocab=cfg.vocab)
    B, S = args.batch, args.prompt_len
    prompts = jnp.asarray(lm_batch(ds, B, S, 0)[:, :S])
    enc = (jnp.zeros((B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
           if cfg.encoder_layers else None)

    cache_len = S + args.gen
    t0 = time.time()
    if enc is not None:
        logits, state, aux = jax.block_until_ready(
            model_prefill_pad(prefill, params, prompts, cache_len, enc))
    else:
        logits, state, aux = jax.block_until_ready(
            model_prefill_pad(prefill, params, prompts, cache_len))
    t_pref = time.time() - t0
    # named SiteAux/LayerAux fields; zero_frac guards the n_blocks == 0
    # (no block-divisible site) case internally
    n_blocks = float(aux.n_blocks)
    zebra_zero_frac = float(aux.zero_frac)
    measured_bytes = float(aux.measured_bytes_exact())  # exact past 16 MiB
    if backend in ("stream", "fused"):
        state = transport_state_compressed(state, cfg,
                                           validation=args.validate)
    # first token gets its own fold (2^32-1 can't collide with the scan's
    # per-step fold_in(key, i), i < gen)
    tok = _next_token(logits, args.temperature,
                      jax.random.fold_in(key, 2**32 - 1))

    t0 = time.time()
    if args.temperature > 0.0:
        toks, state = generate(params, tok, state, jnp.int32(S), key)
    else:
        toks, state = generate(params, tok, state, jnp.int32(S))
    jax.block_until_ready(toks)
    t_dec = time.time() - t0
    gen = np.asarray(jnp.concatenate([tok, toks], axis=1))[:, :args.gen]
    print(f"[serve] {cfg.name} batch={B} prompt={S} gen={args.gen}")
    print(f"  prefill: {t_pref*1e3:.1f} ms  decode: "
          f"{t_dec/max(args.gen-1,1)*1e3:.2f} ms/token (single scan dispatch)")
    if n_blocks > 0:
        # block-weighted mean over every prefill Zebra site (ffn_hidden +
        # kv_cache); the kv-cache-only traffic cut is the TOTAL line of the
        # per-leaf transport report above when --backend stream/fused is on
        print(f"  zebra zero-block fraction, all prefill sites: "
              f"{zebra_zero_frac:.3f}")
    else:
        print("  zebra: no block-divisible site this shape — zero-block "
              "fraction n/a")
    if measured_bytes > 0:
        print(f"  zebra in-model transport: {measured_bytes/1e6:.3f} MB "
              f"measured compressed stream bytes (prefill sites)")
    print("  sample continuation:", gen[0, :16].tolist())


_SPOT_CHECK = itertools.count()        # rotates the sampled leaf per call


def validate_state_ingest(cstate, dense_state, level: str,
                          site: str = "serve", breaker=None):
    """Validate every ``CompressedMap`` leaf of a handoff tree at the
    consumer boundary; a corrupt leaf is replaced by its dense source
    (the ``ft.faults`` "recompute-dense" policy, applied per leaf) so one
    bad stream degrades ONE cache's transport instead of failing the
    batch. An armed chaos plan (``ft.inject``) with a stream fault at
    ``site`` corrupts leaves here — after compression, before
    validation — exercising the real ingest path.

    The handoff is also a circuit-breaker boundary: pass a
    ``ft.breaker.BreakerBoard`` (or arm one ambiently via
    ``breaker_scope``) and per-leaf detections feed its trip window;
    with the site OPEN the whole tree degrades to its dense source
    wholesale — no per-leaf validate+fallback — until half-open probes
    pass. Returns ``(tree, n_recovered)``."""
    from ..compress import CompressedMap
    from ..compress.integrity import validate_map
    from ..ft.breaker import active_board
    from ..ft.faults import CorruptStream
    from ..ft.inject import STREAM_KINDS, active_plan, corrupt_map

    is_cm = lambda l: isinstance(l, CompressedMap)
    dense_leaves = jax.tree_util.tree_leaves(dense_state)
    c_leaves, treedef = jax.tree_util.tree_flatten(cstate, is_leaf=is_cm)
    board = breaker if breaker is not None else active_board()
    if board is not None:
        board.tick()                        # call-counted breaker clock
        if not board.allow(site):
            out = [d if is_cm(c) else c
                   for d, c in zip(dense_leaves, c_leaves)]
            return jax.tree_util.tree_unflatten(treedef, out), 0
    plan = active_plan()
    out, n_bad = [], 0
    for i, (d, c) in enumerate(zip(dense_leaves, c_leaves)):
        if not is_cm(c):
            out.append(c)
            continue
        if plan is not None:
            f = plan.take(STREAM_KINDS, site)
            if f is not None:
                c = corrupt_map(c, f.kind, arg=f.arg)
                plan.note(f.kind, site)
        try:
            validate_map(c, level=level, site=f"{site}:leaf{i}")
            out.append(c)
            if board is not None and level != "off":
                board.record_success(site)
        except CorruptStream as e:
            n_bad += 1
            if board is not None:
                board.record_failure(site)
            print(f"[serve] {e} — leaf {i} recovered from its dense source")
            out.append(d)
    return jax.tree_util.tree_unflatten(treedef, out), n_bad


def transport_state_compressed(state, cfg, sample_leaf: int | None = None,
                               validation: str = "off"):
    """The prefill -> decode handoff in compressed stream form: pack every
    compatible cache leaf (lossless nonzero-block bitmap), count the bytes
    actually moved, reconcile against Eq. 2/3, and hand the caches to the
    decode loop IN PAYLOAD FORM — the ``CompressedMap`` pytree itself
    crosses the jit boundary, and ``steps.make_generate`` unpacks it
    inside the decode dispatch. Losslessness (pinned exhaustively by
    tests/test_compress.py) is spot-checked on one sampled leaf so the
    handoff doesn't pay a second full decompression for a print — the
    sample rotates across calls within a process (long-running servers /
    test suites cover every leaf; pin one with ``sample_leaf``). The Eq.
    2/3 reconcile bound is asserted for EVERY leaf individually —
    ``meter.reconcile`` raises on the first leaf outside it."""
    from ..compress import (BandwidthMeter, CompressedMap, compress_tree,
                            decompress)

    caches, enc_out = state
    meter = BandwidthMeter()
    ccaches = compress_tree(caches, bs=cfg.zebra_block_seq,
                            bc=cfg.zebra_block_ch, meter=meter, site="kv",
                            checksum=(validation == "checksum"))
    is_cm = lambda l: isinstance(l, CompressedMap)
    sampled = [(a, c) for a, c in zip(
        jax.tree_util.tree_leaves(caches),
        jax.tree_util.tree_leaves(ccaches, is_leaf=is_cm)) if is_cm(c)]
    idx = 0
    ok = True
    if sampled:
        idx = (next(_SPOT_CHECK) if sample_leaf is None else sample_leaf) \
            % len(sampled)
        ok = bool(jnp.array_equal(sampled[idx][0], decompress(sampled[idx][1])))
    # raises per leaf if any measured-predicted delta leaves the
    # index-padding bound (+1 B float-roundoff slack) — no leaf can hide
    # behind the max in the report below
    rec = meter.reconcile(tol_bytes_per_map=1.0)
    print("[serve] compressed KV-cache transport (prefill -> decode, "
          "payload form):")
    print(meter.report())
    print(f"  lossless (sampled leaf {idx + 1}/{max(len(sampled), 1)}): {ok}"
          f"  reconcile: {rec['n_sites']} sites, every leaf within the "
          f"index-padding bound, max |measured - predicted| = "
          f"{rec['max_abs_delta_bytes']:.2f} B")
    if rec["n_sites"] == 0:
        print("  WARNING: no cache leaf was block-divisible — every leaf "
              "moved dense; pick batch/prompt-len/gen so that "
              "batch*(prompt+gen) divides by zebra_block_seq")
    if validation != "off":
        (ccaches, n_bad) = validate_state_ingest(ccaches, caches, validation)
        print(f"  ingest validation ({validation}): "
              f"{'clean' if n_bad == 0 else f'{n_bad} leaf(s) recovered dense'}")
    return ccaches, enc_out


def model_prefill_pad(prefill_fn, params, prompts, cache_len, enc=None,
                      bucket=True):
    """prefill builds a cache sized to the prompt; pad it to cache_len so
    decode can run. (One jit'd pad via device_put keeps shardings.)

    ``cache_len`` is bucketed up to the power-of-two ladder
    (``serve.bucket.pow2_bucket`` — the same helper the continuous
    engine's cache ladder uses) so downstream decode jits, which key on
    the padded cache shape, compile at most once per bucket instead of
    once per distinct ``prompt+gen`` total. End-padding past the
    requested length is position-correct: the decode mask never attends
    beyond ``pos``. ``bucket=False`` keeps the exact length."""
    if enc is not None:
        logits, (caches, enc_out), aux = prefill_fn(params, prompts, enc)
    else:
        logits, (caches, enc_out), aux = prefill_fn(params, prompts)
    S = prompts.shape[1]
    if bucket:
        cache_len = pow2_bucket(max(cache_len, S), lo=8)
    pad = cache_len - S

    def padk(x):
        if x.ndim >= 4 and x.shape[-3] == S:   # (.., B, T, H, hd) attn caches
            cfgpad = [(0, 0)] * x.ndim
            cfgpad[-3] = (0, pad)
            return jnp.pad(x, cfgpad)
        return x
    caches = jax.tree_util.tree_map(padk, caches)
    return logits, (caches, enc_out), aux


def serve_continuous(args, cfg, mesh, model, params, trace=None) -> dict:
    """``--requests N``: run a synthetic heavy-traffic trace (or the given
    ``trace``) through the continuous-batching engine, print its
    throughput report and return it."""
    from ..ft import FTConfig
    from ..serve import ServeEngine, synthetic_trace

    eng = ServeEngine(model, params, mesh, n_slots=args.slots,
                      max_cache_len=pow2_ceil(args.prompt_len + args.gen),
                      page_tokens=args.page_tokens,
                      validation=args.validate,
                      temperature=args.temperature, seed=args.seed,
                      queue_bound=args.queue_bound)
    if trace is None:
        trace = synthetic_trace(
            args.requests, vocab=cfg.vocab, seed=args.seed,
            prompt_lo=max(args.prompt_len // 4, 4), prompt_hi=args.prompt_len,
            gen_lo=max(args.gen // 4, 1), gen_hi=args.gen,
            deadline_ticks=args.deadline_ticks or None)
    ft_cfg = FTConfig(jitter_seed=args.seed) if args.supervise else None
    rep = eng.run(trace, preempt_after=args.preempt_after, ft_cfg=ft_cfg)
    print(f"[serve] {cfg.name} continuous: {rep['n_requests']} requests "
          f"({rep['n_rejected']} rejected, {rep['n_shed']} shed, "
          f"{rep['deadline_misses']} deadline misses) in "
          f"{rep['wall_s']:.2f} s over {args.slots} slots")
    print(f"  {rep['requests_per_s']:.2f} req/s  {rep['tokens_per_s']:.1f} "
          f"tok/s  p50 {rep['p50_token_ms']:.1f} ms/token  "
          f"p95 {rep['p95_token_ms']:.1f} ms/token  "
          f"evictions {rep['evictions']}")
    print(f"  KV stream: {rep['kv_bytes_measured']/1e6:.3f} MB measured "
          f"(dense {rep['kv_bytes_dense']/1e6:.3f} MB) over "
          f"{rep['kv_pages']} pages, zero-block fraction "
          f"{rep['zero_frac']:.3f}, {rep['pages_recovered']} pages "
          f"recovered dense")
    print(f"  dispatch shapes: decode {rep['decode_shapes']}"
          f"/{rep['decode_shape_bound']}  prefill {rep['prefill_shapes']}"
          f"/{rep['prefill_shape_bound']}  reconcile max "
          f"|measured-predicted| {rep['reconcile_max_delta_bytes']:.2f} B")
    return rep


if __name__ == "__main__":
    main()
