"""The Zebra block gate (Shih & Chang, arXiv:2205.00779, Sec. II),
written out plainly: split a map into non-overlapping blocks, keep a
block when its largest magnitude reaches the threshold, zero it
otherwise."""
from __future__ import annotations

import jax.numpy as jnp


def gate_rows(x, thr: float, bs: int, bc: int):
    """(S, D) token map, blocks of ``bs`` rows by ``bc`` columns."""
    S, D = x.shape
    m = jnp.max(jnp.abs(x).reshape(S // bs, bs, D // bc, bc), axis=(1, 3))
    keep = m >= thr
    mask = jnp.repeat(jnp.repeat(keep, bs, axis=0), bc, axis=1)
    return jnp.where(mask, x, 0.0), keep


def gate_nchw(x, thr: float, b: int):
    """(B, C, H, W) map, ``b x b`` spatial blocks per channel."""
    B, C, H, W = x.shape
    m = jnp.max(jnp.abs(x).reshape(B, C, H // b, b, W // b, b), axis=(3, 5))
    keep = m >= thr
    mask = jnp.repeat(jnp.repeat(keep, b, axis=2), b, axis=3)
    return jnp.where(mask, x, 0.0), keep
