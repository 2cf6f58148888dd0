"""Plain reference of the served StarCoder2 stack (arXiv:2402.19173).

Float32 ``jax.numpy`` at ``Precision.HIGHEST``, one layer at a time, no
kernels, no cache, no batching. It follows the published block: pre-norm
LayerNorm, grouped-query attention with rotary embeddings (theta
``rope_theta``), a tanh-GELU MLP, a tied output head. The served
configuration adds the Zebra gate at two sites:

* the FFN hidden map, in blocks of ``block_seq`` tokens by ``block_ch``
  channels where a prompt prefix is prefilled, and of one token by
  ``block_ch`` channels where a token is decoded alone;
* the K/V a prefill writes to its cache (heads folded onto channels),
  in ``block_seq`` x ``block_ch`` blocks. The prefill's own attention
  reads the K/V before the gate; every later token reads the gated ones.

So a request whose prompt has ``P`` tokens runs as the server runs it: a
prefill of the first ``Pb`` tokens, then one token at a time from
position ``fed``. Given the whole sequence (prompt and served tokens),
this module computes every decoded position at once: the
one-token-at-a-time part is causal, so it is one masked pass over those
positions whose keys are the gated prefill K/V before ``fed`` and their
own K/V from ``fed`` on.

Departures from the published model, each as served: the input embedding
is multiplied by ``sqrt(hidden_size)``; attention is global, which equals
the published 4096-token sliding window for sequences of at most 4096
positions; the attention output projection has no bias. LayerNorm scales
and all biases hold their initial values (1 and 0) in the served
weights, so they are left out here.

Weights are not read from the server: ``weights`` draws them from the
seed's key by the recipe the served weights follow (normal draws in
bfloat16 scaled by ``1/sqrt(fan_in)``, one key split per tensor).

``forward`` runs in one of two ways.

* On its own (``outputs=None``): the gate keeps a block when its
  largest magnitude reaches the threshold, and the pass returns what a
  server's output would hold: the cache rows, the prefill's last-row
  logits and the served rows' logits. At a lower precision mode this is
  the control put in the program's place.
* Decision-matched (``outputs`` given, float32): at every site, layer
  and block the pass takes the decision the output under test took, and
  compares its arithmetic under those decisions. A block's decision at
  the K/V site shows in the output: the cache holds a dropped block as
  all zeros. At the FFN site it shows in what the block moves: the next
  layer's K/V rows of the same positions (the cache), and at the last
  layer the prefill's last-row logits. Starting from its own decisions,
  the pass flips a block's decision where that brings those rows closer
  to the output's (to first order in the block's contribution, from the
  exact rows, ``RECOVER_PASSES`` times). A block moves its rows by a
  whole block of the map, many times the rounding of the arithmetic, so
  the decision recovered is the one taken. Blocks that move nothing the output holds
  (the last layer's, but for the prefill's last row group) keep the
  pass's own decisions. The decisions are then judged on their own: at
  each site and layer, the blocks whose taken decision differs from the
  threshold on the decision-matched map, and how far such a block's
  largest magnitude lies from the threshold.

Every request runs at one shape: its prefix padded to ``kmax`` rows and
its decoded rows to ``lbmax``, the largest the traffic has, which the
caller gives (causality keeps the padding out of every real row). So
one cell compiles each program once, and a new seed compiles nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .precision import einsum

WDT = jnp.bfloat16          # the dtype the served weights are drawn in
RECOVER_PASSES = 2          # first-order flip passes recovering FFN decisions


def sizes(c: dict) -> dict:
    d, nq = c["hidden_size"], c["num_attention_heads"]
    return dict(d=d, nq=nq, nkv=c["num_key_value_heads"], hd=d // nq,
                f=c["intermediate_size"], V=c["vocab_size"],
                L=c["num_hidden_layers"], theta=float(c["rope_theta"]),
                eps=float(c["norm_epsilon"]))


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, WDT) * float(np.sqrt(1.0 / fan_in))


def weights(key, c: dict):
    """(embedding (V, d), per-layer weights stacked on a leading axis)."""
    s = sizes(c)
    d, nq, nkv, hd, f = s["d"], s["nq"], s["nkv"], s["hd"], s["f"]

    def layer(k):
        k = jax.random.split(k, 1)[0]
        k8 = jax.random.split(k, 8)
        a = jax.random.split(k8[0], 4)
        m = jax.random.split(k8[2], 4)
        return {"wq": _normal(a[0], (d, nq, hd), d),
                "wk": _normal(a[1], (d, nkv, hd), d),
                "wv": _normal(a[2], (d, nkv, hd), d),
                "wo": _normal(a[3], (nq, hd, d), nq * hd),
                "w_up": _normal(m[1], (d, f), f),
                "w_down": _normal(m[2], (f, d), f)}

    def build(key):
        ks = jax.random.split(key, 1024)
        embed = jax.random.normal(ks[0], (s["V"], d), WDT) * (d ** -0.5)
        if s["L"] > 1:
            layers = jax.vmap(layer)(jnp.stack(jax.random.split(ks[1], s["L"])))
        else:
            layers = jax.tree_util.tree_map(lambda x: x[None], layer(ks[1]))
        return embed, layers
    return jax.jit(build)(key)


def _ln(x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, pos, theta):
    """x (S, H, hd), pos (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def unrope(x, theta: float):
    """Host-side inverse of ``_rope`` for rows at positions ``0..S-1``:
    x (S, H, hd) -> float32 rows as they were before the rotation."""
    x = np.asarray(x, np.float32)
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = np.arange(x.shape[0], dtype=np.float32)[:, None] * freqs
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * cos + x2 * sin, x2 * cos - x1 * sin], -1)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


def _attend(q, k, v, ok, nkv, mode):
    """q (S, nq, hd), k/v (T, nkv, hd), ok (S, T) -> (S, nq, hd)."""
    S, nq, hd = q.shape
    qg = q.reshape(S, nkv, nq // nkv, hd) * (hd ** -0.5)
    s = einsum("shgd,thd->hgst", qg, k, mode)
    s = jnp.where(ok[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = einsum("hgst,thd->shgd", p, v, mode)
    return o.reshape(S, nq, hd)


def _block_max(x, bs: int, bc: int):
    """(S, D) -> the largest magnitude of each (bs x bc) block."""
    S, D = x.shape
    return jnp.max(jnp.abs(x).reshape(S // bs, bs, D // bc, bc), axis=(1, 3))


def _block_sum(x, bs: int, bc: int):
    S, D = x.shape
    return jnp.sum(x.reshape(S // bs, bs, D // bc, bc), axis=(1, 3))


def _expand(keep, bs: int, bc: int):
    return jnp.repeat(jnp.repeat(keep, bs, axis=0), bc, axis=1)


def _f32(w):
    return jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), w)


def _qkv(w, h, pos, s, mode):
    q = _rope(einsum("sd,dhk->shk", h, w["wq"], mode), pos, s["theta"])
    k0 = einsum("sd,dhk->shk", h, w["wk"], mode)
    v = einsum("sd,dhk->shk", h, w["wv"], mode)
    return q, _rope(k0, pos, s["theta"]), k0, v


def _mlp_in(w, x, s, mode):
    return _gelu(einsum("sd,df->sf", _ln(x, s["eps"]), w["w_up"], mode))


@functools.partial(jax.jit, static_argnames=("c_items", "mode"))
def _prefill_attn(w, x, *, c_items, mode):
    """The prefill of a prefix ``x`` (Pb, d) through one layer's
    attention: the residual after it, the FFN hidden map (Pb, f), and
    the prefix's K (roped and not) and V (Pb, nkv, hd) before the gate."""
    s = dict(c_items)
    w = _f32(w)
    pos = jnp.arange(x.shape[0])
    q, k, k0, v = _qkv(w, _ln(x, s["eps"]), pos, s, mode)
    o = _attend(q, k, v, pos[:, None] >= pos[None, :], s["nkv"], mode)
    x = x + einsum("shk,hkd->sd", o, w["wo"], mode)
    return x, _mlp_in(w, x, s, mode), k, k0, v


@functools.partial(jax.jit, static_argnames=("c_items", "given"))
def _kv_site(k, k0, v, out, pb, *, c_items, given):
    """The K/V gate over a prefix's cache rows (R, nkv, hd; the first
    ``pb`` real), by the decisions the output ``out`` shows (``given``;
    (R, 2 nkv hd): unroped K then V, rows it does not hold zero; a block
    it holds as all zeros was dropped) or by the threshold. Returns the
    gated roped K and V (what later tokens read), the gated unroped rows
    (R, 2 nkv hd), and per K and V (count of blocks, of flips, the
    largest flip's distance from the threshold / threshold)."""
    s = dict(c_items)
    bs, bc, thr = s["block_seq"], s["block_ch"], s["thr"]
    R, nkv, hd = k.shape
    dk = nkv * hd
    real = (jnp.arange(R // bs) < pb // bs)[:, None]
    outs, stats = [], []
    for j, (x, x0) in enumerate(((k, k0), (v, v))):
        m = _block_max(x.reshape(R, dk), bs, bc)
        own = m >= thr
        keep = (_block_max(out[:, j * dk:(j + 1) * dk], bs, bc) > 0
                if given else own)
        stats.append(_flip_stats(m, own, keep,
                                 jnp.broadcast_to(real, keep.shape), thr))
        mask = _expand(keep, bs, bc)
        outs.append((x.reshape(R, dk) * mask, x0.reshape(R, dk) * mask))
    (kg, k0g), (vg, _) = outs
    return (kg.reshape(R, nkv, hd), vg.reshape(R, nkv, hd),
            jnp.concatenate([k0g, vg], axis=1), stats)


@functools.partial(jax.jit, static_argnames=("c_items", "mode"))
def _decode_attn(w, x, kA, vA, fed, *, c_items, mode):
    """Rows ``x`` (LB, d) decoded one at a time from position ``fed``
    through one layer's attention; each reads the gated prefix keys
    ``kA``, ``vA`` (rows at or past ``fed`` unread) and its own and the
    earlier decoded rows' K/V. Returns what ``_prefill_attn`` does."""
    s = dict(c_items)
    w = _f32(w)
    LB = x.shape[0]
    iB = jnp.arange(LB)
    q, k, k0, v = _qkv(w, _ln(x, s["eps"]), fed + iB, s, mode)
    ok = jnp.concatenate([
        jnp.broadcast_to(jnp.arange(kA.shape[0])[None, :] < fed,
                         (LB, kA.shape[0])),
        iB[:, None] >= iB[None, :]], axis=1)
    o = _attend(q, jnp.concatenate([kA, k]), jnp.concatenate([vA, v]), ok,
                s["nkv"], mode)
    x = x + einsum("shk,hkd->sd", o, w["wo"], mode)
    return x, _mlp_in(w, x, s, mode), k, k0, v


@functools.partial(jax.jit, static_argnames=("c_items", "bs", "mode"))
def _ffn(w_down, x, h, keep, *, c_items, bs, mode):
    """The residual after the FFN, with the hidden map gated by ``keep``
    in (bs x block_ch) blocks."""
    bc = dict(c_items)["block_ch"]
    u = h * _expand(keep, bs, bc)
    return x + einsum("sf,fd->sd", u, w_down.astype(jnp.float32), mode)


@functools.partial(jax.jit, static_argnames=("c_items", "bs"))
def _own(h, *, c_items, bs):
    s = dict(c_items)
    return _block_max(h, bs, s["block_ch"]) >= s["thr"]


def _flip_stats(m, own, keep, ev, thr):
    """(blocks with evidence, flips among them, the largest flip's
    |block max - thr| / thr)."""
    flip = (own != keep) & ev
    return (jnp.sum(ev), jnp.sum(flip),
            jnp.max(jnp.where(flip, jnp.abs(m - thr) / thr, 0.0)))


@functools.partial(jax.jit, static_argnames=("c_items",))
def _proj_maps(w_down, wk, wv, *, c_items):
    """First-order effect of an FFN block on the next layer's K/V rows:
    ``M`` (f, 2 nkv hd), the (centred) down projection into the next
    layer's unroped K and V at unit LayerNorm scale, and ``G`` (f/bc,
    bc, bc), each block's Gram matrix of ``M``."""
    bc = dict(c_items)["block_ch"]
    d = w_down.shape[1]
    w2 = w_down.astype(jnp.float32)
    w2 = w2 - jnp.mean(w2, axis=1, keepdims=True)
    wkv = jnp.concatenate([wk.reshape(d, -1), wv.reshape(d, -1)],
                          axis=1).astype(jnp.float32)
    M = einsum("fd,dk->fk", w2, wkv)
    Mb = M.reshape(-1, bc, M.shape[1])
    return wkv, M, einsum("bik,bjk->bij", Mb, Mb)


def _lnorm(r, eps):
    mu = jnp.mean(r, axis=-1, keepdims=True)
    sig = jnp.sqrt(jnp.mean(jnp.square(r - mu), axis=-1, keepdims=True) + eps)
    return (r - mu) / sig, sig


@functools.partial(jax.jit, static_argnames=("c_items", "bs"))
def _match_kv(w_down, wkv, M, G, x, h, out, count, *, c_items, bs):
    """The FFN decisions (x: residual before the FFN, h: hidden map) the
    output took, recovered from the next layer's unroped K/V rows it
    holds (``out`` (R, 2 nkv hd), its first ``count`` rows; in a prefix,
    entries of a K/V block it dropped are left out). Returns the
    decisions, the blocks that show evidence of theirs, and the flip
    statistics (``_flip_stats``)."""
    s = dict(c_items)
    bc, thr, eps = s["block_ch"], s["thr"], s["eps"]
    R, f = h.shape
    row_ok = jnp.arange(R) < count
    nb = f // bc
    dk = out.shape[1] // 2
    ent = jnp.broadcast_to(row_ok[:, None], out.shape)
    if bs > 1:
        held = jnp.concatenate([_block_max(out[:, :dk], bs, bc),
                                _block_max(out[:, dk:], bs, bc)], axis=1) > 0
        ent = ent & _expand(held, bs, bc)
    rows = row_ok.astype(jnp.float32)[:, None]
    h3 = h.reshape(R, nb, bc)
    q = jnp.einsum("rbi,bij,rbj->rb", h3, G, h3,
                   precision=jax.lax.Precision.HIGHEST) * rows
    ev = jnp.max(rows.reshape(R // bs, bs), axis=1)[:, None] > 0
    ev = jnp.broadcast_to(ev, (R // bs, nb))
    m = _block_max(h, bs, bc)
    own = m >= thr
    keep = own
    w2 = w_down.astype(jnp.float32)
    for _ in range(RECOVER_PASSES):
        r = x + einsum("sf,fd->sd", h * _expand(keep, bs, bc), w2)
        n, sig = _lnorm(r, eps)
        e = jnp.where(ent, out - einsum("sd,dk->sk", n, wkv), 0.0)
        P = einsum("sk,fk->sf", e / sig, M)
        ip = _block_sum(h * P, bs, bc)
        nrm = jnp.sum((q / jnp.square(sig)).reshape(R // bs, bs, nb), axis=1)
        gain = 2.0 * jnp.where(keep, -ip, ip) - nrm
        keep = jnp.where(ev & (gain > 0), ~keep, keep)
    return keep, ev, _flip_stats(m, own, keep, ev, thr)


@functools.partial(jax.jit, static_argnames=("c_items", "bs"))
def _match_logits(w_down, embed, x, h, out, pb, *, c_items, bs):
    """At the last layer, the decisions of the prefix's last row group
    (its last row ``pb - 1``), recovered from the prefill's last-row
    logits the output holds (``out`` (V,)); every other block keeps the
    threshold's decision and shows no evidence. Returns what
    ``_match_kv`` does."""
    s = dict(c_items)
    bc, thr, eps = s["block_ch"], s["thr"], s["eps"]
    R, f = h.shape
    nb = f // bc
    m = _block_max(h, bs, bc)
    own = m >= thr
    w2 = w_down.astype(jnp.float32)
    E = embed.astype(jnp.float32)
    g = (pb - 1) // bs
    hl = h[pb - 1].reshape(nb, bc)
    w2c = (w2 - jnp.mean(w2, axis=1, keepdims=True)).reshape(nb, bc, -1)
    D = einsum("bd,vd->bv", jnp.einsum("bj,bjd->bd", hl, w2c,
                          precision=jax.lax.Precision.HIGHEST), E)
    nrm = jnp.sum(jnp.square(D), axis=1)
    kl = own[g]
    for _ in range(RECOVER_PASSES):
        r = x[pb - 1] + einsum("f,fd->d", h[pb - 1] * jnp.repeat(kl, bc), w2)
        n, sig = _lnorm(r, eps)
        e = out - einsum("d,vd->v", n, E)
        ip = einsum("bv,v->b", D, e) / sig
        gain = 2.0 * jnp.where(kl, -ip, ip) - nrm / jnp.square(sig)
        kl = jnp.where(gain > 0, ~kl, kl)
    keep = own.at[g].set(kl)
    ev = jnp.zeros_like(own).at[g].set(True)
    return keep, ev, _flip_stats(m, own, keep, ev, thr)


@jax.jit
def _kv_sums(ref, out, count):
    """Squared error and squared reference over the first ``count``
    rows, for K and V apart: (R, 2 nkv hd) each."""
    ok = (jnp.arange(ref.shape[0]) < count)[:, None]
    dk = ref.shape[1] // 2
    d2 = jnp.where(ok, jnp.square(out - ref), 0.0)
    r2 = jnp.where(ok, jnp.square(ref), 0.0)
    return jnp.stack([jnp.sum(d2[:, :dk]), jnp.sum(r2[:, :dk]),
                      jnp.sum(d2[:, dk:]), jnp.sum(r2[:, dk:])])


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_last(embed, xA, pb, eps, mode):
    row = jax.lax.dynamic_slice_in_dim(xA, pb - 1, 1)
    return einsum("sd,vd->sv", _ln(row, eps), embed.astype(jnp.float32),
                  mode)[0]


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _served(embed, xB, tok, eps, mode):
    """Per decoded row: the gap of ``tok`` below the best logit, the
    best's lead over the second, and the best token."""
    logits = einsum("sd,vd->sv", _ln(xB, eps), embed.astype(jnp.float32),
                    mode)
    top, idx = jax.lax.top_k(logits, 2)
    got = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
    return top[:, 0] - got, top[:, 0] - top[:, 1], idx[:, 0]


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(embed, ids, scale):
    return embed[ids].astype(jnp.float32) * scale


SITES = ("ffn_hidden", "kv_cache")


def forward(c: dict, zebra: dict, key, reqs, *, kmax: int, lbmax: int,
            mode="f32", outputs=None, weights_=None):
    """The reference over each request, all requests layer by layer.

    ``reqs``: ``[(ids, P, Pb, fed, n), ...]``: the whole sequence (prompt
    then served tokens), the prompt length, the prefill length, the
    first position decoded alone and the number of served tokens;
    ``kmax`` and ``lbmax`` the rows every prefix and every request's
    decoded part are padded to.
    ``outputs``: None (the pass decides by itself), or per request the
    output under test ``(k, v, logits, tokens)``: the cache rows it left
    ``(L, >= P + n - 1, nkv, hd)``, the prefill's last-row logits
    ``(V,)`` and the ``n`` tokens it served, whose decisions the pass
    takes (float32 only).

    Yields ``(i, res)``: ``res["pre"]`` the prefill's last-row logits
    ``(V,)``; ``res["gap"]``, ``res["lead"]``, ``res["top"]`` per served
    token (served row ``j`` is at position ``P - 1 + j`` and predicts
    served token ``j``): the gap of that token below the best logit, the
    best's lead over the second and the best token (``gap`` is of the
    output's own served token where ``outputs`` names them). With
    ``outputs``: ``res["kv"]`` per layer (squared K error, squared K,
    the same for V) over the rows the output holds, and
    ``res["flips"]`` per site a list of (layer, blocks, flips, largest
    relative distance of a flip from the threshold), one or more per
    layer.
    Without: ``res["k"]``, ``res["v"]`` the cache rows ``(L, P + n - 1,
    nkv, hd)`` a server would hold. ``res["keep"]`` per layer the FFN
    decisions taken, of the prefix (block rows) and of the decoded rows
    (one row each, to the row bucket), and with ``outputs`` ``res["ev"]``
    the same shapes: the blocks whose decision shows in the output.
    """
    s = sizes(c)
    L = s["L"]
    thr = float(np.asarray(zebra["t_obj"], jnp.dtype(zebra["map_dtype"])))
    bs = int(zebra["block_seq"])
    items = tuple(sorted({**s, "block_seq": bs, "thr": thr,
                          "block_ch": zebra["block_ch"]}.items()))
    if outputs is not None and mode != "f32":
        raise ValueError("the decision-matched pass runs in float32")
    embed, layers = weights_ if weights_ is not None else weights(key, c)
    scale = float(s["d"] ** 0.5)
    dkv = 2 * s["nkv"] * s["hd"]
    st = []
    for ids, P, Pb, fed, n in reqs:
        end = P + n - 1                 # positions fed .. end-1 decode alone
        if not (1 <= Pb <= kmax and end - fed <= lbmax):
            raise ValueError(f"request of prefill {Pb} and {end - fed} "
                             f"decoded rows outside {kmax}, {lbmax}")
        LB = lbmax
        idsA = np.zeros(kmax, np.int32)
        idsA[:Pb] = ids[:Pb]
        idsB = np.zeros(LB, np.int32)
        idsB[:end - fed] = ids[fed:end]
        tok = np.zeros(LB, np.int32)
        tok[:end - fed] = ids[fed + 1:end + 1]
        if outputs is not None:
            lo = P - 1 - fed
            tok[lo:lo + n] = outputs[len(st)][3]
        st.append({"xA": _embed(embed, jnp.asarray(idsA), scale),
                   "xB": _embed(embed, jnp.asarray(idsB), scale),
                   "tok": tok, "end": end, "LB": LB,
                   "kv": [], "flips": {k: [] for k in SITES},
                   "rows": ([], []), "keep": [], "ev": []})

    def held(i, li):
        """The output's layer-``li`` rows of request ``i`` as the
        prefix's and the decoded rows' targets, on the device."""
        ids, P, Pb, fed, n = reqs[i]
        r = st[i]
        k, v = outputs[i][:2]
        end = r["end"]
        rows = np.concatenate([unrope(k[li][:end], s["theta"]).reshape(end, -1),
                               np.asarray(v[li][:end], np.float32
                                          ).reshape(end, -1)], axis=1)
        tA = np.zeros((kmax, dkv), np.float32)
        tA[:fed] = rows[:fed]
        tB = np.zeros((r["LB"], dkv), np.float32)
        tB[:end - fed] = rows[fed:end]
        return jnp.asarray(tA), jnp.asarray(tB)

    def stat(li, x):
        return (li,) + tuple(float(t) for t in x)

    with jax.default_matmul_precision("highest"):
        targets = ({i: held(i, 0) for i in range(len(reqs))}
                   if outputs is not None else None)
        for li in range(L):
            w = jax.tree_util.tree_map(lambda t: t[li], layers)
            nxt = None
            if outputs is not None and li + 1 < L:
                wn = jax.tree_util.tree_map(lambda t: t[li + 1], layers)
                nxt = _proj_maps(w["w_down"], wn["wk"], wn["wv"],
                                 c_items=items)
            for i, (ids, P, Pb, fed, n) in enumerate(reqs):
                r = st[i]
                end, LB = r["end"], r["LB"]
                fed_ = jnp.int32(fed)
                xA, hA, kA, kA0, vA = _prefill_attn(w, r["xA"], c_items=items,
                                                    mode=mode)
                tA, tB = targets[i] if targets is not None else (None, None)
                kg, vg, rowsA, kst = _kv_site(
                    kA, kA0, vA, tA, jnp.int32(Pb), c_items=items,
                    given=outputs is not None)
                xB, hB, kB, kB0, vB = _decode_attn(w, r["xB"], kg, vg, fed_,
                                                   c_items=items, mode=mode)
                rowsB = jnp.concatenate([kB0.reshape(LB, -1),
                                         vB.reshape(LB, -1)], axis=1)
                if outputs is None:
                    rk, rv = r["rows"]
                    rk.append(np.concatenate([np.asarray(kg)[:fed],
                                              np.asarray(kB)[:end - fed]]))
                    rv.append(np.concatenate([np.asarray(vg)[:fed],
                                              np.asarray(vB)[:end - fed]]))
                    keepA = _own(hA, c_items=items, bs=bs)
                    keepB = _own(hB, c_items=items, bs=1)
                    evA = evB = None
                else:
                    sums = (np.asarray(_kv_sums(rowsA, tA, fed_), np.float64)
                            + np.asarray(_kv_sums(rowsB, tB,
                                                  jnp.int32(end - fed)),
                                         np.float64))
                    r["kv"].append(tuple(float(t) for t in sums))
                    r["flips"]["kv_cache"] += [stat(li, x) for x in kst]
                    if nxt is not None:
                        wkv, M, G = nxt
                        nA, nB = held(i, li + 1)
                        keepA, evA, fa = _match_kv(
                            w["w_down"], wkv, M, G, xA, hA, nA, fed_,
                            c_items=items, bs=bs)
                        keepB, evB, fb = _match_kv(
                            w["w_down"], wkv, M, G, xB, hB, nB,
                            jnp.int32(end - fed), c_items=items, bs=1)
                        targets[i] = (nA, nB)
                        r["flips"]["ffn_hidden"] += [stat(li, fa),
                                                     stat(li, fb)]
                    else:
                        keepA, evA, fa = _match_logits(
                            w["w_down"], embed, xA, hA,
                            jnp.asarray(outputs[i][2], jnp.float32),
                            jnp.int32(Pb), c_items=items, bs=bs)
                        keepB = _own(hB, c_items=items, bs=1)
                        evB = jnp.zeros_like(keepB)
                        r["flips"]["ffn_hidden"].append(stat(li, fa))
                r["keep"].append((np.asarray(keepA), np.asarray(keepB)))
                if evA is not None:
                    r["ev"].append((np.asarray(evA), np.asarray(evB)))
                r["xA"] = _ffn(w["w_down"], xA, hA, keepA, c_items=items,
                               bs=bs, mode=mode)
                r["xB"] = _ffn(w["w_down"], xB, hB, keepB, c_items=items,
                               bs=1, mode=mode)
        for i, (ids, P, Pb, fed, n) in enumerate(reqs):
            r = st[i]
            lo = P - 1 - fed
            gap, lead, top = (np.asarray(t)[lo:lo + n] for t in _served(
                embed, r["xB"], jnp.asarray(r["tok"]), s["eps"], mode))
            res = {"pre": np.asarray(_head_last(embed, r["xA"], jnp.int32(Pb),
                                                s["eps"], mode)),
                   "gap": gap, "lead": lead, "top": top,
                   "keep": r["keep"]}
            if outputs is None:
                res["k"], res["v"] = (np.stack(x) for x in r["rows"])
            else:
                res["kv"], res["flips"], res["ev"] = (r["kv"], r["flips"],
                                                      r["ev"])
            st[i] = None                # free the request's rows
            yield i, res
