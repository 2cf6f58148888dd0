"""Jit'd public wrappers around the Pallas kernels.

Whether a kernel is compiled by Mosaic or run by the Pallas interpreter
follows the platform (``kernels.platform.pallas_interpret``: compiled on
a TPU, interpreted on any other backend); no wrapper takes an option for
it. ``zebra_ffn_hidden`` is the fused "Zebra site + downstream matmul".
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .mask_pack import zebra_mask_pack
from .pack import zebra_pack, zebra_unpack
from .spmm_cs import zebra_spmm_cs
from .zebra_mask import zebra_mask
from .zebra_spmm import zebra_spmm
from . import ref


def zebra_mask_op(x: jax.Array, t_obj: float, bs: int = 8, bc: int = 128):
    """(..., M, K) tolerant wrapper; flattens leading dims onto M."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    y, bm = zebra_mask(x2, t_obj=t_obj, bs=bs, bc=bc)
    return y.reshape(shape), bm


def zebra_spmm_op(x: jax.Array, w: jax.Array, bitmap: jax.Array,
                  bs: int = 8, bc: int = 128, stm: int | None = None,
                  stk: int | None = None,
                  caps: tuple[int, ...] | None = None,
                  zero_frac_hint: float | None = None,
                  scheduled: bool | None = None):
    return zebra_spmm(x, w, bitmap, bs=bs, bc=bc, stm=stm, stk=stk,
                      caps=caps, zero_frac_hint=zero_frac_hint,
                      scheduled=scheduled)


def zebra_pack_op(x: jax.Array, bitmap: jax.Array, bs: int = 8, bc: int = 128):
    """Compact live blocks of a masked (M, K) map -> (payload, n_live)."""
    return zebra_pack(x, bitmap, bs=bs, bc=bc)


def zebra_unpack_op(payload: jax.Array, bitmap: jax.Array, bs: int = 8,
                    bc: int = 128):
    return zebra_unpack(payload, bitmap, bs=bs, bc=bc)


def zebra_mask_pack_op(x: jax.Array, t_obj: float, bs: int = 8, bc: int = 128,
                       tm: int | None = None, tk: int | None = None):
    """Two-phase parallel producer: (M, K) -> (payload, bitmap, n_live)."""
    return zebra_mask_pack(x, t_obj=t_obj, bs=bs, bc=bc, tm=tm, tk=tk)


def zebra_spmm_cs_op(payload: jax.Array, w: jax.Array, bitmap: jax.Array,
                     bs: int = 8, bc: int = 128, stm: int | None = None,
                     stk: int | None = None,
                     caps: tuple[int, ...] | None = None,
                     zero_frac_hint: float | None = None,
                     scheduled: bool | None = None):
    """Compressed-stream consumer: payload x (K, N) -> (M, N) fp32."""
    return zebra_spmm_cs(payload, w, bitmap, bs=bs, bc=bc, stm=stm, stk=stk,
                         caps=caps, zero_frac_hint=zero_frac_hint,
                         scheduled=scheduled)


def zebra_ffn_hidden(x: jax.Array, w_out: jax.Array, t_obj: float,
                     bs: int = 8, bc: int = 128):
    """Fused: h' = zebra(h); y = h' @ W_out, skipping dead blocks.

    Streaming form: the two-phase mask_pack producer emits the
    compressed stream (no dense masked intermediate) and the supertiled
    GEMM consumes the payload."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    payload, bm, _ = zebra_mask_pack(x2, t_obj=t_obj, bs=bs, bc=bc)
    y = zebra_spmm_cs(payload, w_out, bm, bs=bs, bc=bc)
    return y.reshape(*shape[:-1], w_out.shape[-1]), bm
