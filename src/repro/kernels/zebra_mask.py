"""Pallas TPU kernel: fused Zebra comparator (paper Fig. 3, inference mode).

One HBM pass: load a ``(TM, TK)`` activation tile into VMEM, compute the
per-``(bs, bc)``-block max, compare against the threshold, zero dead blocks
in-register, write the tile and its keep-bitmap back. This is the paper's
RTL comparator recast as a VMEM-tiled epilogue.

Tiling: the kernel tile (TM, TK) contains an integer number of Zebra
blocks; the engine sizes it with ``ZebraConfig.tiles_for``.

Bitmap layout on the device. Mosaic writes an output block only when its
last two dims are multiples of (8, 128) or span the whole array, and it
cannot relayout a narrow boolean vector into int8. So each grid step
writes its ``(TM/bs, TK/bc)`` keep flags as int32 into its own slice of a
per-tile ``(GM, GK, TM/bs, TK/bc)`` array — the trailing dims span the
array, whatever the tile — and one XLA reshape outside the kernel turns
that into the ``(M/bs, K/bc)`` int8 bitmap (1/256 of the map's bytes at
(8, 128) bf16 blocks). Block maxima are taken in f32 against the
threshold rounded to the map's dtype, which is the same comparison the
reference makes in that dtype.

On a TPU the blocks must cover whole (8, 128) vreg tiles
(``platform.tpu_tileable``); the engine sends narrower blocks (the
paper's 4x4 NCHW blocks) to the reference path there. Off a TPU the
kernel runs interpreted for any block shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..utils import cdiv
from .platform import pallas_interpret


def threshold(t_obj: float, dtype) -> float:
    """T_obj rounded to the map's dtype, as the f32 the kernels compare
    block maxima against (exact: every bf16/f32 value is an f32)."""
    return float(np.asarray(t_obj, dtype=jnp.dtype(dtype)))


def tile_blockmax(x: jax.Array, bs: int, bc: int) -> jax.Array:
    """(TM, TK) tile -> (TM/bs, TK/bc) f32 block maxima of |x|: a sublane
    reduction over each block's bs rows, then a lane reduction over its
    bc columns (the two reshapes Mosaic can lay out)."""
    a = jnp.abs(x.astype(jnp.float32))
    tm, tk = a.shape
    rows = jnp.max(a.reshape(tm // bs, bs, tk), axis=1)
    return jnp.max(rows.reshape(tm // bs, tk // bc, bc), axis=2)


def tile_bitmap(bm4: jax.Array, nm: int, nk: int) -> jax.Array:
    """Per-tile keep flags (GM, GK, TM/bs, TK/bc) -> the (nm, nk) int8
    bitmap; rows/columns from padded edge tiles are dropped."""
    GM, GK, tmb, tkb = bm4.shape
    bm = bm4.transpose(0, 2, 1, 3).reshape(GM * tmb, GK * tkb)
    return bm[:nm, :nk].astype(jnp.int8)


def block_max(x: jax.Array, bs: int, bc: int) -> jax.Array:
    """(M, K) -> (M/bs, K/bc) block maxima of |x|, in x's dtype."""
    M, K = x.shape
    return jnp.max(jnp.abs(x.reshape(M // bs, bs, K // bc, bc)), axis=(1, 3))


def block_keep(x: jax.Array, t_obj: float, bs: int, bc: int) -> jax.Array:
    """The comparator in XLA: (M, K) -> (M/bs, K/bc) int8 keep bitmap,
    for observables read off an already-masked map."""
    blockmax = block_max(x, bs, bc)
    return (blockmax >= jnp.asarray(t_obj, blockmax.dtype)).astype(jnp.int8)


def expand_blocks(blocks: jax.Array, bs: int, bc: int) -> jax.Array:
    """(M/bs, K/bc) per-block values -> (M, K) elementwise broadcast."""
    return jnp.repeat(jnp.repeat(blocks, bs, axis=0), bc, axis=1)


def _zebra_mask_kernel(x_ref, y_ref, bm_ref, *, thr: float, bs: int, bc: int):
    x = x_ref[...]
    TM, TK = x.shape
    keep = (tile_blockmax(x, bs, bc) >= thr).astype(jnp.float32)
    tmb, tkb = keep.shape
    full = jnp.broadcast_to(keep[:, None, :, None],
                            (tmb, bs, tkb, bc)).reshape(TM, TK)
    y_ref[...] = (x.astype(jnp.float32) * full).astype(x.dtype)
    bm_ref[0, 0] = keep.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("t_obj", "bs", "bc", "tm", "tk"))
def zebra_mask(x: jax.Array, *, t_obj: float, bs: int = 8, bc: int = 128,
               tm: int = 256, tk: int = 512
               ) -> tuple[jax.Array, jax.Array]:
    """(M, K) -> (masked (M, K), keep bitmap (M//bs, K//bc) int8)."""
    M, K = x.shape
    tm = min(tm, M)
    tk = min(tk, K)
    if M % bs or K % bc:
        raise ValueError(f"(M={M}, K={K}) must divide by block ({bs},{bc})")
    if tm % bs or tk % bc:
        raise ValueError(f"tile ({tm},{tk}) must divide by block ({bs},{bc})")
    nm, nk = M // bs, K // bc
    GM, GK = cdiv(M, tm), cdiv(K, tk)
    kernel = functools.partial(_zebra_mask_kernel,
                               thr=threshold(t_obj, x.dtype), bs=bs, bc=bc)
    y, bm4 = pl.pallas_call(
        kernel,
        grid=(GM, GK),
        in_specs=[pl.BlockSpec((tm, tk), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((tm, tk), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1, tm // bs, tk // bc),
                         lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, K), x.dtype),
            jax.ShapeDtypeStruct((GM, GK, tm // bs, tk // bc), jnp.int32),
        ],
        interpret=pallas_interpret(),
        name="zebra_mask",
    )(x)
    return y, tile_bitmap(bm4, nm, nk)
