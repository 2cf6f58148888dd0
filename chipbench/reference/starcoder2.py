"""Plain reference of the served StarCoder2 stack (arXiv:2402.19173).

Float32 ``jax.numpy`` at ``Precision.HIGHEST``, one layer at a time, no
kernels, no cache, no batching. It follows the published block: pre-norm
LayerNorm, grouped-query attention with rotary embeddings (theta
``rope_theta``), a tanh-GELU MLP, a tied output head. The served
configuration adds the Zebra gate (``reference/gate.py``) at two sites:

* the FFN hidden map, in blocks of ``block_seq`` tokens by ``block_ch``
  channels where a prompt prefix is prefilled, and of one token by
  ``block_ch`` channels where a token is decoded alone;
* the K/V a prefill writes to its cache (heads folded onto channels),
  in ``block_seq`` x ``block_ch`` blocks. The prefill's own attention
  reads the K/V before the gate; every later token reads the gated ones.

So a request whose prompt has ``P`` tokens runs as the server runs it: a
prefill of the first ``Pb`` tokens, then one token at a time from
position ``fed``. Given the whole sequence (prompt and served tokens),
this module computes the logits at every decoded position at once: the
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

``forward`` gives what the check compares with the served path: the
logits of the prefill's last row, the logits at every served position,
and, layer by layer, the K/V rows a server's cache holds for the request
once its last token is served.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .gate import gate_rows
from .precision import einsum, rounded

WDT = jnp.bfloat16          # the dtype the served weights are drawn in


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


@functools.partial(jax.jit, static_argnames=("c_items", "thr", "mode"))
def _layer(w, xA, xB, fed, *, c_items, thr, mode):
    """One layer over a request: ``xA`` (Pb, d) the prefilled prefix,
    ``xB`` (LB, d) the positions decoded one at a time from ``fed``
    (rows past the request's end are padding; causality keeps them out
    of every real row). Also returns the layer's cache rows: the gated
    prefill K and V ``(Pb, nkv, hd)`` and the decoded ones ``(LB, nkv,
    hd)``."""
    s = dict(c_items)
    nq, nkv, hd, eps, theta = s["nq"], s["nkv"], s["hd"], s["eps"], s["theta"]
    bs, bc = s["block_seq"], s["block_ch"]
    w = jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), w)
    r = functools.partial(rounded, mode=mode)

    def qkv(h, pos):
        q = _rope(einsum("sd,dhk->shk", h, w["wq"], mode), pos, theta)
        k = _rope(einsum("sd,dhk->shk", h, w["wk"], mode), pos, theta)
        v = einsum("sd,dhk->shk", h, w["wv"], mode)
        return q, k, v

    def mlp(h, rows):
        u = _gelu(einsum("sd,df->sf", h, w["w_up"], mode))
        u, _ = gate_rows(u, thr, rows, bc)
        return einsum("sf,fd->sd", r(u), w["w_down"], mode)

    Pb, LB = xA.shape[0], xB.shape[0]
    # -- the prefill of the first Pb tokens
    posA = jnp.arange(Pb)
    q, k, v = qkv(_ln(xA, eps), posA)
    causal = posA[:, None] >= posA[None, :]
    xA = xA + einsum("shk,hkd->sd", _attend(q, k, v, causal, nkv, mode),
                     w["wo"], mode)
    kg, _ = gate_rows(k.reshape(Pb, nkv * hd), thr, bs, bc)
    vg, _ = gate_rows(v.reshape(Pb, nkv * hd), thr, bs, bc)
    xA = xA + mlp(_ln(xA, eps), bs)
    # -- the tokens decoded one at a time, from position fed
    posB = fed + jnp.arange(LB)
    q, k, v = qkv(_ln(xB, eps), posB)
    keys = jnp.concatenate([kg.reshape(Pb, nkv, hd), k])
    vals = jnp.concatenate([vg.reshape(Pb, nkv, hd), v])
    iB = jnp.arange(LB)
    ok = jnp.concatenate([jnp.broadcast_to(posA[None, :] < fed, (LB, Pb)),
                          iB[:, None] >= iB[None, :]], axis=1)
    xB = xB + einsum("shk,hkd->sd", _attend(q, keys, vals, ok, nkv, mode),
                     w["wo"], mode)
    xB = xB + mlp(_ln(xB, eps), 1)
    kv = (kg.reshape(Pb, nkv, hd), vg.reshape(Pb, nkv, hd), k, v)
    return xA, xB, kv


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(embed, xB, eps, mode):
    h = _ln(xB, eps)
    return einsum("sd,vd->sv", h, embed.astype(jnp.float32), mode)


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(embed, ids, scale):
    return embed[ids].astype(jnp.float32) * scale


def _bucket(n: int, lo: int = 128) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


@jax.jit
def gap(logits, tok):
    """Gap of ``tok`` below the best logit, per row."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
    return best - got


@jax.jit
def margin(logits):
    """The best logit's lead over the second, per row."""
    top = jax.lax.top_k(logits, 2)[0]
    return top[:, 0] - top[:, 1]


def forward(c: dict, zebra: dict, key, reqs, *, modes=("f32",),
            on_kv=None, weights_=None):
    """The reference over each request, at each precision mode of
    ``modes`` in lockstep (``precision.MODES``).

    ``reqs``: ``[(ids, P, Pb, fed, n), ...]``: the whole sequence (prompt
    then served tokens), the prompt length, the prefill length, the
    first position decoded alone and the number of served tokens.
    ``on_kv(i, layer, {mode: (kA, vA, kB, vB)})`` gets each layer's cache
    rows (``_layer``); row ``j`` of ``kB`` is position ``fed + j``.
    Yields ``(i, {mode: (prefill logits (V,), served logits (n, V))})``:
    served row ``j`` predicts served token ``j``, at position
    ``P - 1 + j``.
    """
    s = sizes(c)
    thr = float(np.asarray(zebra["t_obj"], jnp.dtype(zebra["map_dtype"])))
    items = tuple(sorted({**s, "block_seq": zebra["block_seq"],
                          "block_ch": zebra["block_ch"]}.items()))
    embed, layers = weights_ if weights_ is not None else weights(key, c)
    scale = float(s["d"] ** 0.5)
    states = {m: [] for m in modes}
    for ids, P, Pb, fed, n in reqs:
        if Pb < 1:
            raise ValueError("the reference expects a prefilled prefix")
        end = P + n - 1                 # positions fed .. end-1 decode alone
        idsB = np.zeros(_bucket(end - fed), np.int32)
        idsB[:end - fed] = ids[fed:end]
        x = [_embed(embed, jnp.asarray(ids[:Pb]), scale),
             _embed(embed, jnp.asarray(idsB), scale)]
        for m in modes:
            states[m].append(list(x))
    with jax.default_matmul_precision("highest"):
        for li in range(s["L"]):
            w = jax.tree_util.tree_map(lambda t: t[li], layers)
            for i, (_, P, Pb, fed, n) in enumerate(reqs):
                kv = {}
                for m in modes:
                    st = states[m][i]
                    st[0], st[1], kv[m] = _layer(
                        w, st[0], st[1], jnp.int32(fed), c_items=items,
                        thr=thr, mode=m)
                if on_kv is not None:
                    on_kv(i, li, kv)
        for i, (_, P, Pb, fed, n) in enumerate(reqs):
            lo = P - 1 - fed
            out = {}
            for m in modes:
                xA, xB = states[m][i]
                out[m] = (_head(embed, xA[-1:], s["eps"], m)[0],
                          _head(embed, xB, s["eps"], m)[lo:lo + n])
            for m in modes:
                states[m][i].clear()        # free the request's rows
            yield i, out
