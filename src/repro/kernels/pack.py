"""Pallas TPU kernels: compressed activation transport (pack / unpack).

``zebra_pack`` compacts the *surviving* ``(bs, bc)`` blocks of a
Zebra-masked ``(M, K)`` map into a dense payload — live blocks first, in
the **GEMM-consumable consumer order** of ``kernels.schedule`` (grouped
by K-block column, columns ascending, rows ascending within a column) —
so the accelerator moves only ``n_live * bs * bc * itemsize`` payload
bytes plus the 1-bit-per-block index (paper Eq. 2/3) instead of the full
map, AND the downstream GEMM reads each K column's operand as one
contiguous slot run with zero dynamic-window gathers on its hot path.
``zebra_unpack`` is the exact inverse. Stream format: README.md
§Compressed activation transport.

Because JAX shapes are static, the payload buffer is allocated at the
worst case (``n_blocks`` slots); the *measured* stream length is
``n_live`` slots and everything past it is zeroed (slot order cannot
change the stream length). Compaction runs as a scatter through the
output BlockSpec index_map: block ``(r, k)``'s destination slot is
``schedule.slot_map``'s consumer-order prefix sum (scalar-prefetched in
SMEM). The grid iterates **K-block columns outermost** so the slot map
stays monotone along the traversal: dead blocks write to the slot the
*next* live block of their column also maps to, and the sequential TPU
grid makes the live block's write win — the dual of the consumers'
revolving-door read trick. Visits to each output slot remain a single
contiguous run of grid steps, which is what the TPU output-revisiting
rule requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import pallas_interpret, tpu_forms
from .schedule import slot_map
from .supertile import gather_supertiles, validate_supertile


def _pack_kernel(dmap_ref, keep_ref, x_ref, out_ref):
    del dmap_ref, keep_ref
    out_ref[...] = x_ref[...][None]


def _unpack_kernel(smap_ref, keep_ref, *refs, R: int, C: int, bs: int,
                   nk: int):
    """Supertiled expander step: scatter the (stm, stk) supertile's R*C
    dynamically slotted payload windows back to their dense positions,
    zero-gating dead blocks (whose revolving-door windows alias live
    slots)."""
    del smap_ref                        # consumed by the BlockSpec index maps
    p_refs, out_ref = refs[:R * C], refs[R * C]
    i, kc = pl.program_id(0), pl.program_id(1)
    rows = []
    for r in range(R):
        cols = []
        for j in range(C):
            live = keep_ref[(i * R + r) * nk + kc * C + j] != 0
            blk = p_refs[r * C + j][...][0]
            cols.append(jnp.where(live, blk, jnp.zeros_like(blk)))
        rows.append(cols[0] if C == 1 else jnp.concatenate(cols, 1))
    out_ref[...] = rows[0] if R == 1 else jnp.concatenate(rows, 0)


def _prefix(bitmap: jax.Array) -> tuple[jax.Array, jax.Array]:
    """keep flags + the consumer-order block -> payload-slot map (THE one
    slot map, from kernels.schedule — producer, expander and consumers
    all address the stream through it)."""
    return slot_map(bitmap)


def expand_payload(payload: jax.Array, keep: jax.Array, smap: jax.Array,
                   nm: int, nk: int, bs: int, bc: int) -> jax.Array:
    """THE XLA blocked expansion of a compressed stream back to the dense
    (M, K) map — shared by zebra_unpack's XLA form and
    zebra_spmm_cs's expand prologue, so the two cannot diverge.

    jnp.where, not multiplication: a dead block's revolving-door slot
    aliases a live block, and masking by * would leak NaN/Inf (and
    -0.0) from it where the kernel form writes exact +0."""
    blocks = jnp.where((keep != 0)[:, None, None], payload[smap],
                       jnp.zeros((), payload.dtype))
    return (blocks.reshape(nm, nk, bs, bc).transpose(0, 2, 1, 3)
            .reshape(nm * bs, nk * bc))


@functools.partial(jax.jit, static_argnames=("bs", "bc"))
def zebra_pack(x: jax.Array, bitmap: jax.Array, *, bs: int = 8, bc: int = 128
               ) -> tuple[jax.Array, jax.Array]:
    """Compact live blocks of a masked (M, K) map.

    Returns (payload (n_blocks, bs, bc) — live blocks first in consumer
    order (column-grouped; kernels.schedule), zero tail — and n_live ()
    int32).
    """
    M, K = x.shape
    if M % bs or K % bc:
        raise ValueError(f"(M={M}, K={K}) must divide by block ({bs},{bc})")
    nm, nk = M // bs, K // bc
    assert bitmap.shape == (nm, nk), (bitmap.shape, nm, nk)
    nb = nm * nk
    keep, dmap = _prefix(bitmap)
    n_live = jnp.sum(keep)

    # K-block column outermost: the consumer-order slot map is monotone
    # along this traversal (ascending within each column's run), which the
    # scatter-through-BlockSpec output-revisiting trick requires.
    payload = pl.pallas_call(
        _pack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nk, nm),
            in_specs=[
                pl.BlockSpec((bs, bc), lambda kc, i, dmap, keep: (i, kc)),
            ],
            out_specs=pl.BlockSpec(
                (1, bs, bc),
                lambda kc, i, dmap, keep: (dmap[i * nk + kc], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nb, bs, bc), x.dtype),
        interpret=pallas_interpret(),
        name="zebra_pack",
    )(dmap, keep, x)

    # Slots >= n_live hold either stale dead-block writes or uninitialized
    # memory; zero them so the stream (and comparisons) are deterministic.
    live_slot = jnp.arange(nb)[:, None, None] < n_live
    payload = jnp.where(live_slot, payload, jnp.zeros((), x.dtype))
    return payload, n_live.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bs", "bc", "stm", "stk",
                                             "payload_windows"))
def zebra_unpack(payload: jax.Array, bitmap: jax.Array, *, bs: int = 8,
                 bc: int = 128, stm: int | None = None, stk: int | None = None,
                 payload_windows: bool | None = None) -> jax.Array:
    """Inverse of zebra_pack: (n_blocks, bs, bc) payload -> dense (M, K).

    Two executable realizations of the one contract (see mask_pack.py):
    ``payload_windows=True`` is the TPU form — the grid steps over
    ``(stm, stk)`` supertiles (``tiles_for(kind="gather")``; the engine
    passes its budgeted tiles, standalone calls use the default-budget
    chooser) and each step writes its own dense window from R*C
    dynamically slotted payload windows. Off a TPU the default runs the
    identical expansion as one XLA blocked gather (the Pallas
    interpreter charges ~100 us per dynamically-indexed window fetch,
    so the gather is the faster realization of the same dataflow on
    CPU, bit for bit)."""
    nm, nk = bitmap.shape
    assert payload.shape == (nm * nk, bs, bc), (payload.shape, nm, nk, bs, bc)
    M, K = nm * bs, nk * bc
    keep, smap = _prefix(bitmap)
    if payload_windows is None:
        payload_windows = tpu_forms(bs, bc)
    if not payload_windows:
        return expand_payload(payload, keep, smap, nm, nk, bs, bc)

    item = jnp.dtype(payload.dtype).itemsize
    dstm, dstk = gather_supertiles(M, K, bs, bc, item)
    stm, stk = stm or dstm, stk or dstk
    validate_supertile(M, K, bs, bc, stm, stk)
    R, C = stm // bs, stk // bc

    def _p_idx(i, kc, smap, keep, *, r, j):
        # dead block: revolving-door fetch of an arbitrary valid slot,
        # zeroed in-kernel (exclusive prefix sum <= n_live <= nb - 1
        # whenever a dead block exists, so the index stays in bounds).
        return (smap[(i * R + r) * nk + kc * C + j], 0, 0)

    return pl.pallas_call(
        functools.partial(_unpack_kernel, R=R, C=C, bs=bs, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nm // R, nk // C),
            in_specs=[pl.BlockSpec((1, bs, bc),
                                   functools.partial(_p_idx, r=r, j=j))
                      for r in range(R) for j in range(C)],
            out_specs=pl.BlockSpec((stm, stk),
                                   lambda i, kc, smap, keep: (i, kc)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, K), payload.dtype),
        interpret=pallas_interpret(),
        name="zebra_unpack",
    )(smap, keep, *([payload] * (R * C)))
