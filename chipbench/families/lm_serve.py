"""Family ``lm_serve``: a decoder-only LM served by ``repro.serve.ServeEngine``.

The engine is built as ``launch.serve.serve_continuous`` builds it (the
launcher's ``serve_config`` for the served config; one engine, reused for
every round), with weights drawn on the device by the program's
``LM.init`` from the run's seed.

Traffic is closed rounds: every request of a round is due at the round's
start, and ``ServeEngine.run`` serves the round to its end. Set-up
(``_warm_up``) runs every program the window will run, so the window
compiles nothing. The window runs whole rounds until ``seconds`` have
passed (the round that crosses the mark finishes); it spans the first
round's start to the last round's end.

End-to-end metrics (host clock):

* ``tokens_per_s``: served tokens of the window / window seconds;
* ``ttft_p<q>_ms`` for q in ``TTFT_PERCENTILES``: that percentile over
  the window's requests of round start -> first served token (queueing
  included: all are due at the round's start). A cell declares the
  highest q that leaves ten requests beyond it in its smallest window;
  the count is printed as ``ttft_samples``;
* ``itl_p95_ms``: p95 over every gap between consecutive served tokens
  (count ``itl_samples``);
* ``setup_s``: process start -> first timed request.

Correctness: requests of the first round, drawn from the seed with its
longest among them, are checked. While the window runs, ``_Capture``
keeps what the timed path produced for them: the prefill program's
logits, and at retirement the request's cache lane as the decode program
left it (the prefix after the pool's page-out/page-in round trip, then
every decoded position). After the window the program's state is freed
and ``readings`` compares those, and the served tokens, with the plain
reference (``reference/starcoder2.py``) in its decision-matched pass:
the reference takes the gate decisions the served path took at every
site, layer and block, so the errors measure the arithmetic, and the
decisions are judged on their own (``flip_share``, ``flip_margin``).
Each number the cell's limits file names is compared with its limit.

With ``trace``, set-up is the same, and one round is served with the
harness's spans around the engine's layers; the profiler records the
round's first ``trace_ticks`` engine ticks (the traffic file's number),
and the per-layer readers get that trace plus counters of the same
ticks.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import time

import numpy as np

from chipbench import generator

SPAN = "chipbench."
TTFT_PERCENTILES = (50, 80, 90, 95)


def seed_key(seed: int):
    import jax
    s = int(seed) % 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(s % 2 ** 32), s >> 32)


def lm_config(c: dict):
    """The program's LMConfig for a configuration file, through the
    launcher's own ``serve_config``; fails if any stated size or Zebra
    setting is not what the program will run."""
    from repro import configs
    from repro.launch import serve as launch
    sv, z = c["served"], c["served"]["zebra"]
    args = launch.parse_args(["--arch", sv["arch"], "--backend", z["backend"],
                              "--t-obj", repr(z["t_obj"])])
    base = configs.get(sv["arch"]).replace(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        zebra_block_seq=z["block_seq"], zebra_block_ch=z["block_ch"],
        zebra_tnet=False, **sv.get("program", {}))
    cfg = launch.serve_config(args, base)
    want = {"n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "head_dim": c["hidden_size"] // c["num_attention_heads"],
            "norm": "layernorm", "act": "gelu", "qkv_bias": True,
            "tie_embeddings": bool(c["tie_word_embeddings"]),
            "param_dtype": sv["dtype"], "compute_dtype": sv["dtype"],
            "zebra_backend": z["backend"], "zebra_t_obj": z["t_obj"],
            "zebra_sites": tuple(z["sites"]), "zebra_tnet": False,
            "layer_pattern": ("global",)}
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"the program would not run the stated "
                         f"configuration: {bad}")
    return cfg


def build(cfg, key):
    """``launch.serve.build`` with the run's key: mesh, model, params."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_host_mesh
    from repro.models.lm import LM
    mesh = make_host_mesh(model=1)
    model = LM(cfg)
    params = jax.jit(model.init)(key)
    pshard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        shd.param_specs(params, cfg, mesh), is_leaf=lambda x: isinstance(x, P))
    return mesh, model, jax.device_put(params, pshard)


class _Rids:
    def __init__(self):
        self.n = 0

    def requests(self, pairs):
        from repro.serve.scheduler import Request
        out = []
        for prompt, max_new in pairs:
            out.append(Request(rid=self.n, prompt=prompt, max_new=max_new))
            self.n += 1
        return out


class _Spans:
    """Host spans around the engine's layers (``jax.profiler``
    annotations on the trace's clock) and the counters the per-layer
    readers take, for one traced slice: from ``start`` (the round's
    start) through ``ticks`` engine ticks (decode dispatches), when the
    profiler stops. Installed on one engine instance."""

    def __init__(self, eng, ticks: int, tdir):
        import jax
        self.jax = jax
        self.ticks, self.tdir = int(ticks), str(tdir)
        self.on = False
        self.t0 = self.t1 = None
        self.pool_s = 0.0
        self.admits = 0
        self.prefills = []          # (Pb, aux, kv zero blocks) per call
        self.decode_pos = []        # each decoded token's position
        self.steps = 0              # ticks in the slice
        self._kv_zero = jax.jit(_kv_zero_blocks, static_argnames=("bs", "bc"))
        self.bs, self.bc = eng.cfg.zebra_block_seq, eng.cfg.zebra_block_ch
        pool = eng.pool
        self._wrap(eng, "_schedule", "schedule")
        self._wrap(eng, "_retire", "retire")
        self._wrap(eng, "report", "report")
        self._wrap(eng, "_admit_tree", "admit", count=True)
        self._wrap(pool, "page_out", "pool.page_out", timed=True)
        self._wrap(pool, "page_in", "pool.page_in", timed=True)
        self._wrap(eng, "_step", "decode_step")
        step, prefill = eng._step, eng._prefill

        def traced_step(now):
            on = self.on
            if on:
                self.decode_pos += [r.pos for r in eng._lanes if r is not None]
            out = step(now)
            if on:
                self.steps += 1
                if self.steps >= self.ticks:
                    self.stop()
            return out

        def traced_prefill(params, prompt):
            with jax.profiler.TraceAnnotation(SPAN + "prefill"):
                out = prefill(params, prompt)
            if self.on:
                _, (caches, _), aux = out
                self.prefills.append((int(prompt.shape[1]), aux,
                                      self._kv_zero(caches, bs=self.bs,
                                                    bc=self.bc)))
            return out
        eng._step, eng._prefill = traced_step, traced_prefill

    def start(self) -> None:
        self.jax.profiler.start_trace(self.tdir)
        self._window = self.jax.profiler.TraceAnnotation(SPAN + "window")
        self._window.__enter__()
        self.on, self.t0 = True, time.time()

    def stop(self) -> None:
        """End the slice (after its last tick, or with the round)."""
        if not self.on:
            return
        self._window.__exit__(None, None, None)
        self.on, self.t1 = False, time.time()
        self.jax.profiler.stop_trace()

    def _wrap(self, obj, attr, name, timed=False, count=False):
        fn = getattr(obj, attr)
        jax = self.jax

        def wrapped(*a, **k):
            on = self.on
            if count and on:
                self.admits += 1
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(SPAN + name):
                out = fn(*a, **k)
            if timed and on:
                self.pool_s += time.perf_counter() - t0
            return out
        setattr(obj, attr, wrapped)


class _Capture:
    """What the timed path produces for the checked requests, kept as it
    is produced: the prefill program's logits (its last row) and, at
    retirement, the request's cache lane as the decode program left it.
    Installed on one engine instance; holds device arrays until ``host``.
    """

    def __init__(self, eng, rids):
        self.rids = set(rids)
        self.logits, self.lanes = {}, {}
        self._cur = None
        self._prefill_len = eng._prefill_bucket
        admit, prefill, retire = eng._admit_tree, eng._prefill, eng._retire

        def admit_tree(r):
            self._cur = r.rid
            try:
                return admit(r)
            finally:
                self._cur = None

        def prefill_kept(params, prompt):
            out = prefill(params, prompt)
            if self._cur in self.rids:
                self.logits[self._cur] = out[0]
            return out

        def retire_kept(now):
            for lane, r in enumerate(eng._lanes):
                if r is not None and r.done and r.rid in self.rids:
                    self.lanes[r.rid] = eng._take_lane(lane)
            return retire(now)
        eng._admit_tree, eng._prefill, eng._retire = (admit_tree,
                                                      prefill_kept,
                                                      retire_kept)

    def host(self, reqs) -> list:
        """The checked requests that finished, with what was kept, on the
        host (``Checked``)."""
        out = []
        for r in reqs:
            if r.rid not in self.logits or r.rid not in self.lanes:
                continue
            P, n = r.prompt_len, len(r.out)
            pb = self._prefill_len(P)
            end = P + n - 1
            (run,) = self.lanes[r.rid]          # one run of attention layers
            kv = [np.asarray(sub[x][:, 0, :end]) for sub in run.values()
                  for x in ("k", "v")]
            if len(kv) != 2:
                raise ValueError("the check reads one run of one layer type")
            out.append(Checked(
                ids=np.concatenate([np.asarray(r.prompt, np.int32),
                                    np.asarray(r.out, np.int32)]),
                P=P, pb=pb, fed=min(pb, P - 1), n=n,
                logits=np.asarray(self.logits[r.rid], np.float32)[0],
                k=kv[0].astype(np.float32), v=kv[1].astype(np.float32),
                tokens=np.asarray(r.out, np.int32)))
        self.logits.clear()
        self.lanes.clear()
        return out


@dataclasses.dataclass
class Checked:
    """One checked request: its sequence (prompt then served tokens), the
    prompt and prefill lengths, the first position decoded alone, the
    served count; and what the output under test holds for it: the
    prefill's last-row logits ``(V,)``, the cache rows ``(layers, P + n -
    1, nkv, hd)`` the decode program left, and the ``n`` tokens served
    (for the program, the sequence's own)."""
    ids: np.ndarray
    P: int
    pb: int
    fed: int
    n: int
    logits: np.ndarray
    k: np.ndarray
    v: np.ndarray
    tokens: np.ndarray

    @property
    def seq(self):
        return self.ids, self.P, self.pb, self.fed, self.n

    @property
    def output(self):
        return self.k, self.v, self.logits, self.tokens


def checked_requests(reqs, tr, seed) -> list:
    """The requests of the first round the check compares:
    ``check_requests`` drawn from the seed, always with the one that
    serves the most tokens."""
    longest = max(range(len(reqs)),
                  key=lambda i: (reqs[i].max_new, reqs[i].prompt_len))
    pick = generator.staged_sample(len(reqs), seed, int(tr["check_requests"]),
                                   longest)
    return [reqs[i] for i in pick]


def _rel(got, want) -> float:
    """||got - want|| / ||want|| in float64."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def ref_rows(tr: dict, prefill_len) -> tuple[int, int]:
    """The rows the reference pads every request's prefix and decoded
    part to (``kmax``, ``lbmax``): the longest prefill of the traffic's
    sizes by the engine's own rule ``prefill_len`` (``ServeEngine.
    _prefill_bucket``), and the bucket of the most rows a request decodes
    one at a time."""
    from repro.serve.bucket import pow2_bucket
    kmax, lb = 8, 1
    for P, n in generator.round_sizes(tr):
        pb = prefill_len(P)
        kmax = max(kmax, pb)
        lb = max(lb, P + n - 1 - min(pb, P - 1))
    return kmax, pow2_bucket(lb, lo=128)


NUMBERS = ("prefill_logit_err", "kv_err", "max_logit_gap", "flip_share",
           "flip_margin")


def readings(c: dict, key, checked: list, rows: tuple[int, int],
             taken=None):
    """The numbers the check compares for an output under test (the
    program's, or the control's in its place), against the reference's
    decision-matched pass; and the reference's least lead of its best
    logit over the second at a served position.

    ``taken`` (per request, the FFN decisions the output is known to
    have taken, as the reference's ``res["keep"]``) adds
    ``recovered_miss``: the blocks whose decision shows in the output
    and was recovered otherwise, of ``recovered_shown``.

    * ``prefill_logit_err``: the prefill's last-row logits,
      ||got - ref|| / ||ref||, worst request;
    * ``kv_err``: the cache's K or V rows at every position of a request,
      prefilled and decoded, ||got - ref|| / ||ref||, worst layer, K or V
      and request;
    * ``max_logit_gap``: the widest gap of a served token below the
      reference's best logit at its position;
    * ``flip_share``: at each Zebra site, over its layers and the checked
      requests, the share of blocks whose decision the output took
      differs from the threshold's on the decision-matched map; worst
      site (a layer alone can show few blocks: the last shows only the
      prefill's last row group);
    * ``flip_margin``: the largest |block max - T_obj| / T_obj of such a
      block.
    """
    from chipbench.reference import starcoder2 as ref
    out = dict.fromkeys(NUMBERS, 0.0)
    flips: dict = {}
    lead = float("inf")
    miss = shown = 0
    for i, res in ref.forward(c, c["served"]["zebra"], key,
                              [r.seq for r in checked], kmax=rows[0],
                              lbmax=rows[1],
                              outputs=[r.output for r in checked]):
        r = checked[i]
        out["prefill_logit_err"] = max(out["prefill_logit_err"],
                                       _rel(r.logits, res["pre"]))
        for d2k, r2k, d2v, r2v in res["kv"]:
            out["kv_err"] = max(out["kv_err"], float(np.sqrt(d2k / r2k)),
                                float(np.sqrt(d2v / r2v)))
        out["max_logit_gap"] = max(out["max_logit_gap"],
                                   float(np.max(res["gap"])))
        for site, per_layer in res["flips"].items():
            for _, n_ev, n_flip, worst in per_layer:
                acc = flips.setdefault(site, [0.0, 0.0])
                acc[0] += n_ev
                acc[1] += n_flip
                out["flip_margin"] = max(out["flip_margin"], worst)
        lead = min(lead, float(np.min(res["lead"])))
        for got_, ev, want in zip(res["keep"], res["ev"],
                                  taken[i] if taken else ()):
            for k, e, t in zip(got_, ev, want):
                miss += int(np.sum((k != t) & e))
                shown += int(np.sum(e))
    if taken:
        out["recovered_miss"], out["recovered_shown"] = miss, shown
    out["flip_share"] = max((f / b for b, f in flips.values() if b),
                            default=0.0)
    return out, lead


def _kv_zero_blocks(caches, *, bs, bc):
    """All-zero (bs x bc) blocks over a prefill's K/V cache leaves
    ``(..., T, Hkv, hd)``, heads folded onto channels."""
    import jax
    import jax.numpy as jnp
    tot = jnp.float32(0.0)
    for leaf in jax.tree_util.tree_leaves(caches):
        T, d = leaf.shape[-3], leaf.shape[-2] * leaf.shape[-1]
        x = jnp.abs(leaf.astype(jnp.float32)).reshape(-1, T // bs, bs,
                                                        d // bc, bc)
        tot = tot + jnp.sum(jnp.max(x, axis=(2, 4)) == 0)
    return tot


def _warm_up(eng, rids, tr, sizes, vocab, seed):
    """Compile and load every program the window's traffic will run: one
    prefill per prompt bucket of the traffic (behind a request that grows
    the cache to its top bucket), then, at that cache bucket, every batch
    bucket with every lane carried across a rebuild (the engine slices a
    lane out with a static index). The lane rounds use one-token prompts,
    which the engine admits without a prefill, to keep set-up short."""
    from repro.serve.bucket import pow2_bucket, pow2_floor
    rng = generator.rng_for(seed, 3)

    def prompt(n):
        return rng.integers(0, vocab, size=n).astype(np.int32)

    p_max = max(p for p, _ in sizes)
    top = pow2_bucket(p_max + max(m for _, m in sizes), lo=eng.c_lo,
                      hi=eng.cache_ladder[-1])
    first = [(prompt(p_max), max(1, top // 2 + 1 - p_max))]
    by_bucket = {}
    for p, _ in sorted(sizes):
        by_bucket.setdefault(pow2_floor(p), p)
    eng.run(rids.requests(first + [(prompt(p), 1)
                                   for p in by_bucket.values()]))
    short = prompt(1)           # decode-only admission: no prefill, one page
    ladder = eng.batch_ladder
    for b in ladder[:-1]:               # grow b -> 2b: carries lanes 0..b-1
        reqs = rids.requests([(short, 3)] * b + [(short, 1)])
        reqs[-1].arrival = 1
        eng.run(reqs)
    b = ladder[-1]                      # shrink from the top bucket: carry
    for lo in range(0, b, max(b // 2, 1)):   # each half of the lanes
        eng.run(rids.requests([(short, 3 if lo <= i < lo + b // 2 else 1)
                               for i in range(b)]))


def _serve_round(eng, reqs):
    t0 = time.time()
    eng.run(reqs)
    t1 = time.time()
    return t0, t1


def run(cell) -> dict:
    import jax
    from repro.serve import ServeEngine
    from repro.serve.bucket import pow2_ceil

    from chipbench.bench import CompileCounter, memory_peak_bytes

    c, tr = cell.config, cell.traffic
    cfg = lm_config(c)
    vocab = c["vocab_size"]
    sizes = generator.round_sizes(tr)
    max_total = max(p for p, _ in sizes) + max(m for _, m in sizes)
    counter = CompileCounter()

    # -- set-up: weights from the seed, the engine, the warm-up rounds
    t_w = time.time()
    key = seed_key(cell.seed)
    mesh, model, params = build(cfg, key)
    jax.block_until_ready(params)
    t_params = time.time() - t_w
    eng = ServeEngine(model, params, mesh, n_slots=int(tr["slots"]),
                      max_cache_len=pow2_ceil(max_total),
                      page_tokens=int(c["served"]["page_tokens"]),
                      validation="off", temperature=0.0,
                      seed=cell.seed % 2 ** 31, queue_bound=0)
    rids = _Rids()
    tdir = cell.out_dir / f"trace_{cell.name}_{cell.seed}"
    spans = _Spans(eng, tr["trace_ticks"], tdir) if cell.trace else None
    t_w = time.time()
    _warm_up(eng, rids, tr, sizes, vocab, cell.seed)
    t_warm = time.time() - t_w

    # -- the window
    first = rids.requests(generator.closed_round(
        tr, vocab=vocab, seed=cell.seed, round_index=0))
    checked = checked_requests(first, tr, cell.seed)
    capture = _Capture(eng, [r.rid for r in checked])
    done, t_begin, t_end, k = [], None, None, 0
    counter.active = True
    t_begin = time.time()
    setup_s = t_begin - cell.t_start
    while True:
        reqs = first if k == 0 else rids.requests(generator.closed_round(
            tr, vocab=vocab, seed=cell.seed, round_index=k))
        if cell.trace:
            cell.out_dir.mkdir(parents=True, exist_ok=True)
            spans.start()
            t0, t1 = _serve_round(eng, reqs)
            spans.stop()
        else:
            t0, t1 = _serve_round(eng, reqs)
        done.append((t0, t1, reqs))
        t_end = t1
        k += 1
        if cell.trace or t_end - t_begin >= cell.seconds:
            break
    counter.active = False

    all_reqs = [r for _, _, rs in done for r in rs]
    ok = [r for r in all_reqs if r.status == "done"]
    n_tok = sum(len(r.out) for r in ok)
    ttft = [(r.t_first - t0) * 1e3 for t0, _, rs in done for r in rs
            if r.status == "done"]
    itl = [(b - a) * 1e3 for r in ok
           for a, b in zip(r.token_times, r.token_times[1:])]
    window = t_end - t_begin
    e2e = {"tokens_per_s": n_tok / window,
           **{f"ttft_p{q}_ms": float(np.percentile(ttft, q))
              for q in TTFT_PERCENTILES},
           "itl_p95_ms": float(np.percentile(itl, 95)),
           "setup_s": setup_s}
    mem = memory_peak_bytes(int(cell.workload["chips"]))

    counters, rec, breakdown = {}, None, {}
    if cell.trace:
        counters, rec, breakdown = _layer_data(c, spans, tdir)

    # -- correctness: free the program's state, then the reference
    got = capture.host([r for r in checked if r.status == "done"])
    rows = ref_rows(tr, eng._prefill_bucket)
    spans_ticks = spans.steps if spans is not None else 0
    del eng, params, model, spans, capture
    gc.collect()
    t_r = time.time()
    numbers, lead = readings(c, key, got, rows)
    t_ref = time.time() - t_r
    checks = _checks(numbers, cell.limits)
    correct = (passes(checks) and len(got) == len(checked)
               and len(ok) == len(all_reqs))
    notes = {"params_s": round(t_params, 3), "warmup_s": round(t_warm, 3),
             "rounds": k, "window_s": round(window, 3),
             "ttft_samples": len(ttft), "itl_samples": len(itl),
             "compile_requests_in_window": counter.requests,
             "compiles_in_window": counter.compiles,
             "cache_load_s": round(counter.load_s, 3),
             "compile_s": round(counter.compile_s, 3),
             "checked_requests": f"{len(got)} of {len(checked)}",
             "checked_tokens": sum(r.n for r in got),
             "reference_s": round(t_ref, 3),
             "reference_least_lead": round(lead, 5)}
    if counters:
        notes["ffn_zero_frac"] = round(counters["ffn_zero_frac"], 5)
        notes["traced_ticks"] = spans_ticks
        notes["traced_s"] = round(counters["window_s"], 3)
    return {"correct": correct, "attempted": len(all_reqs),
            "failed": len(all_reqs) - len(ok), "e2e": e2e, "checks": checks,
            "trace": rec, "counters": counters, "breakdown": breakdown,
            "memory_peak_bytes": mem, "notes": notes}


def _checks(numbers: dict, limits: dict) -> dict:
    """Each number the cell's limits file names, beside its limit; with no
    limits file every number, with no limit (and the run not correct)."""
    names = list(limits) or list(numbers)
    return {k: {"value": numbers[k], "limit": limits.get(k)} for k in names}


def passes(checks: dict) -> bool:
    return all(v["limit"] is not None and v["value"] <= v["limit"]
               for v in checks.values())


def round_counters(c, prefills, decode_pos, window_s, pool_s, admits):
    """Counters of a traced slice for the per-layer readers. ``prefills``
    lists each prefill call as ``(length, FFN zero blocks over all its
    layers)``; ``decode_pos`` the position of every token the decode
    program processed (teacher-forced or generated)."""
    from chipbench.metrics.lib import counts
    L = c["num_hidden_layers"]
    f = c["intermediate_size"]
    z = c["served"]["zebra"]
    calls = []
    for pb, ffn_zero in prefills:
        nb = L * (pb // z["block_seq"]) * (f // z["block_ch"])
        calls.append({"M": pb, "n_live": nb - ffn_zero, "n_blocks": nb})
    flops = (sum(counts.lm_prefill_flops(c, x["M"]) for x in calls)
             + sum(counts.lm_decode_flops(c, p) for p in decode_pos))
    return {"window_s": window_s, "model_flops": flops,
            "pool_s": pool_s, "admits": admits,
            "prefill_calls": calls, "layers": L,
            "ffn_zero_frac": 1.0 - (sum(x["n_live"] for x in calls)
                                    / max(sum(x["n_blocks"]
                                              for x in calls), 1))}


def _layer_data(c, spans, tdir):
    """Counters of the traced slice, and the reduced trace."""
    from chipbench.metrics.lib import trace as trl
    counters = round_counters(
        c, [(pb, float(aux.zf_blocks) - float(kv0))
            for pb, aux, kv0 in spans.prefills],
        spans.decode_pos, spans.t1 - spans.t0, spans.pool_s, spans.admits)
    rec = trl.extract(trl.find_xplane(str(tdir)))
    shutil.rmtree(tdir, ignore_errors=True)
    trl.save(rec, f"{tdir}.json.gz")
    breakdown = {"device_ops": trl.top(trl.op_seconds(rec)),
                 "idle_gaps": trl.top(trl.idle_by_span(rec))}
    return counters, rec, breakdown
