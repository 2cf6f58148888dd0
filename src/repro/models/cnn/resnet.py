"""ResNet-18 / ResNet-56 (CIFAR/Tiny-ImageNet variants) with Zebra sites.

ResNet-18: stem conv3x3 -> 4 stages of 2 BasicBlocks (64,128,256,512).
ResNet-56: CIFAR style, 3 stages of 9 BasicBlocks (16,32,64).
Zebra is applied after every ReLU (both intra-block and post-residual).
``apply`` runs each layer group under a named scope (``stem``,
``s<stage>b<block>``, ``head``), so every op of the compiled forward
names its layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..layers import (bn_apply, bn_init, conv_apply, conv_init, dense_apply,
                      dense_init, global_avg_pool)
from ...core.zebra import ZebraConfig
from ...core.bandwidth import MapSpec
from .common import ZebraSites, relu, site_block


def _block_init(keys, c_in, c_out, stride):
    p = {
        "conv1": conv_init(next(keys), c_in, c_out, 3),
        "conv2": conv_init(next(keys), c_out, c_out, 3),
    }
    pb1, sb1 = bn_init(c_out)
    pb2, sb2 = bn_init(c_out)
    p["bn1"], p["bn2"] = pb1, pb2
    s = {"bn1": sb1, "bn2": sb2}
    if stride != 1 or c_in != c_out:
        p["proj"] = conv_init(next(keys), c_in, c_out, 1)
        pbp, sbp = bn_init(c_out)
        p["bnp"], s["bnp"] = pbp, sbp
    return p, s


def _block_apply(p, s, x, stride, train, sites, z):
    h = conv_apply(p["conv1"], x, stride=stride)
    h, ns1 = bn_apply(p["bn1"], s["bn1"], h, train)
    h = relu(h)
    h = sites(h, z)
    h = conv_apply(p["conv2"], h)
    h, ns2 = bn_apply(p["bn2"], s["bn2"], h, train)
    if "proj" in p:
        sc = conv_apply(p["proj"], x, stride=stride)
        sc, nsp = bn_apply(p["bnp"], s["bnp"], sc, train)
        new_s = {"bn1": ns1, "bn2": ns2, "bnp": nsp}
    else:
        sc = x
        new_s = {"bn1": ns1, "bn2": ns2}
    y = relu(h + sc)
    y = sites(y, z)
    return y, new_s


class ResNet:
    def __init__(self, stage_sizes, stage_channels, num_classes=10, in_hw=32,
                 width_mult: float = 1.0):
        self.stage_sizes = stage_sizes
        self.stage_channels = [max(8, int(c * width_mult)) for c in stage_channels]
        self.num_classes = num_classes
        self.in_hw = in_hw

    # ---- layout helpers -------------------------------------------------
    def _walk(self):
        """Yield (stage, block, c_in, c_out, stride)."""
        c_in = self.stage_channels[0]
        for si, (n, c) in enumerate(zip(self.stage_sizes, self.stage_channels)):
            for bi in range(n):
                stride = 2 if (si > 0 and bi == 0) else 1
                yield si, bi, c_in, c, stride
                c_in = c

    def init(self, key, zcfg: ZebraConfig = ZebraConfig()):
        keys = iter(jax.random.split(key, 4096))
        sites = ZebraSites(zcfg)
        params, state, zebra = {}, {}, {}
        c0 = self.stage_channels[0]
        params["stem"] = conv_init(next(keys), 3, c0, 3)
        params["bn_stem"], state["bn_stem"] = bn_init(c0)
        name, tnet = sites.init_site(next(keys), c0)
        zebra[name] = tnet
        for si, bi, c_in, c_out, stride in self._walk():
            p, s = _block_init(keys, c_in, c_out, stride)
            params[f"s{si}b{bi}"], state[f"s{si}b{bi}"] = p, s
            for _ in range(2):  # two ReLU sites per block
                name, tnet = sites.init_site(next(keys), c_out)
                zebra[name] = tnet
        params["fc"] = dense_init(next(keys), self.stage_channels[-1], self.num_classes)
        return {"params": params, "state": state, "zebra": zebra}

    def apply(self, variables, x, train: bool, zcfg: ZebraConfig):
        p, s, z = variables["params"], variables["state"], variables.get("zebra")
        sites = ZebraSites(zcfg)
        new_state = {}
        with jax.named_scope("stem"):
            x = conv_apply(p["stem"], x)
            x, new_state["bn_stem"] = bn_apply(p["bn_stem"], s["bn_stem"], x,
                                               train)
            x = relu(x)
            x = sites(x, z)
        for si, bi, c_in, c_out, stride in self._walk():
            nm = f"s{si}b{bi}"
            with jax.named_scope(nm):
                x, new_state[nm] = _block_apply(p[nm], s[nm], x, stride,
                                                train, sites, z)
        with jax.named_scope("head"):
            x = global_avg_pool(x)
            logits = dense_apply(p["fc"], x)
        return logits, new_state, sites.auxes

    def map_specs(self, in_hw: int | None = None, zcfg: ZebraConfig = ZebraConfig()):
        hw = in_hw or self.in_hw
        specs = []

        def add(c, hw):
            b = site_block(hw, hw, zcfg.block_hw)
            specs.append(MapSpec(c=c, h=hw, w=hw, bits=zcfg.act_bits, block=b))

        add(self.stage_channels[0], hw)
        for si, bi, c_in, c_out, stride in self._walk():
            if stride == 2:
                hw //= 2
            add(c_out, hw)   # post-conv1 ReLU
            add(c_out, hw)   # post-residual ReLU
        return specs


def resnet18(num_classes=10, in_hw=32, width_mult=1.0):
    return ResNet([2, 2, 2, 2], [64, 128, 256, 512], num_classes, in_hw, width_mult)


def resnet56(num_classes=10, in_hw=32, width_mult=1.0):
    return ResNet([9, 9, 9], [16, 32, 64], num_classes, in_hw, width_mult)
