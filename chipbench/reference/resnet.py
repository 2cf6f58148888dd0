"""Plain reference of ResNet-18 for Tiny-ImageNet inference, as the Zebra
paper (arXiv:2205.00779, Table 3) evaluates it: a 3x3 stem without
pooling, four stages of two basic blocks (64, 128, 256, 512 channels),
global average pooling and a linear classifier; the Zebra gate
(``reference/gate.py``) after every ReLU, in ``block_hw`` x ``block_hw``
spatial blocks per channel.

Float32 at ``Precision.HIGHEST``, NCHW, no kernels. Batch norm runs in
inference form with its running statistics at their initial values
(mean 0, variance 1) and its scale and bias at 1 and 0, as in the served
weights. Weights are drawn from the seed's key by the recipe the served
weights follow (He-normal float32 draws, one key per tensor in model
order, one key skipped per gate site).

The controls: ``mode="fp8"`` rounds every convolution's and the
classifier's operands to float8 e4m3 (``reference/precision.py``);
``mode="bf16"`` rounds every operand and every intermediate map to
bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .gate import gate_nchw
from .precision import conv, rounded

BN_EPS = 1e-5


def _walk(c):
    chans = c["stage_channels"]
    c_in = chans[0]
    for si, (n, ch) in enumerate(zip(c["stage_blocks"], chans)):
        for bi in range(n):
            yield si, bi, c_in, ch, (2 if (si > 0 and bi == 0) else 1)
            c_in = ch


def _he(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * float(np.sqrt(2.0 / fan_in))


def weights(key, c: dict):
    def build(key):
        keys = iter(jax.random.split(key, 4096))
        c0, k0 = c["stage_channels"][0], c["stem_kernel"]
        w = {"stem": _he(next(keys), (c0, c["in_channels"], k0, k0),
                         c["in_channels"] * k0 * k0)}
        next(keys)                                   # gate site
        for si, bi, c_in, c_out, stride in _walk(c):
            blk = {"conv1": _he(next(keys), (c_out, c_in, 3, 3), c_in * 9),
                   "conv2": _he(next(keys), (c_out, c_out, 3, 3), c_out * 9)}
            if stride != 1 or c_in != c_out:
                blk["proj"] = _he(next(keys), (c_out, c_in, 1, 1), c_in)
            next(keys)                               # two gate sites
            next(keys)
            w[f"s{si}b{bi}"] = blk
        cl = c["stage_channels"][-1]
        w["fc"] = _he(next(keys), (cl, c["num_classes"]), cl)
        return w
    return jax.jit(build)(key)


def _block(hw, b_want):
    b = min(b_want, hw)
    while hw % b:
        b -= 1
    return b


@functools.partial(jax.jit, static_argnames=("c_items", "mode"))
def logits(w, x, *, c_items, mode: str = "f32"):
    c = dict(c_items)
    c["stage_blocks"], c["stage_channels"] = list(c["stage_blocks"]), list(c["stage_channels"])
    thr, bhw = c["t_obj"], c["block_hw"]
    r = functools.partial(rounded, mode=mode if mode == "bf16" else "f32")

    def bn(t):
        return r(t * jax.lax.rsqrt(jnp.float32(1.0 + BN_EPS)))

    def site(t):
        t = r(jax.nn.relu(t))
        return gate_nchw(t, thr, _block(t.shape[2], bhw))[0]

    x = r(x.astype(jnp.float32))
    h = site(bn(r(conv(x, w["stem"], 1, mode))))
    for si, bi, c_in, c_out, stride in _walk(c):
        blk = w[f"s{si}b{bi}"]
        y = site(bn(r(conv(h, blk["conv1"], stride, mode))))
        y = bn(r(conv(y, blk["conv2"], 1, mode)))
        sc = bn(r(conv(h, blk["proj"], stride, mode))) if "proj" in blk else h
        h = site(r(y + sc))
    feat = r(jnp.mean(h, axis=(2, 3)))
    return jnp.dot(rounded(feat, mode), rounded(w["fc"], mode),
                   precision=jax.lax.Precision.HIGHEST)


def items(c: dict, zebra: dict) -> tuple:
    keep = {k: (tuple(v) if isinstance(v, list) else v) for k, v in c.items()
            if k in ("stage_blocks", "stage_channels", "num_classes",
                     "in_channels", "stem_kernel", "image_hw")}
    keep["t_obj"] = float(np.asarray(zebra["t_obj"],
                                     jnp.dtype(zebra["map_dtype"])))
    keep["block_hw"] = int(zebra["block_hw"])
    return tuple(sorted(keep.items()))
