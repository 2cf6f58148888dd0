"""Pallas TPU kernels: two-phase parallel Zebra streaming producer.

``zebra_mask_pack`` turns a raw ``(M, K)`` activation map into the
compressed ``(payload, bitmap, n_live)`` stream — the exact bytes the
paper's accelerator puts on DRAM (Eq. 2/3) — without ever materializing
the dense masked map, in **two fully parallel Pallas passes** bridged by
a tiny XLA exclusive scan:

1. **Comparator pass** (grid over ``tiles_for`` supertiles): each step
   loads its own ``(tm, tk)`` tile, computes per-``(bs, bc)``-block
   maxima and emits the keep bitmap for its tile. Nothing else leaves
   the pass; steps share no state and can run in any order.
2. **Exclusive scan** (XLA, not a launch): the ``kernels.schedule``
   prefix sums over the keep flags are simultaneously the per-column
   live counts, the per-column payload offsets and every block's
   consumer-order slot index ``dmap[g]`` (column-grouped — the
   GEMM-consumable order the consumers read contiguously); a scatter of
   ``g`` into ``dmap[g]`` inverts it into ``src[slot] -> block``.
3. **Pack pass** (grid over payload slot windows): each step *gathers*
   the ``W`` source blocks for its own window of payload slots through
   ``W`` independently-addressed BlockSpecs (``src`` rides in
   scalar-prefetch SMEM) and zeroes the tail past ``n_live``. Every
   step writes only its own ``(W, bs, bc)`` slot range.

Like the consumers, the pack pass has two executable realizations of the
one contract, selected by ``gather_kernel`` (default: the Pallas form
wherever ``kernels.platform.tpu_forms`` holds): elsewhere the identical
gather runs as one XLA blocked take (``xb[src]``) instead, because the
Pallas interpreter charges ~100 us per dynamically-indexed window fetch and
duplicates the ``W`` source operands in its grid carry — the XLA take is
the faster realization of the same dataflow, bit for bit.

Why two-phase beats the online counter: the single-pass design kept a
running SMEM counter as an *online* exclusive prefix sum, which (a)
serialized the whole grid — every step observed the counter state of
all previous steps, so nothing could overlap — and (b) forced the
entire worst-case ``(n_blocks, bs, bc)`` payload to stay VMEM-resident
across the grid (the only way a sequential step could store to slot
``counter``), capping map size at ``vmem_budget_bytes`` and degrading
larger maps to a 3-launch pipeline. Hoisting the prefix sum out of the
kernel into one XLA cumsum removes both: the comparator and pack passes
touch only their own tiles (no cross-step ordering dependence, no
whole-payload residency, any map size), at the cost of reading ``x``
twice — cheap, because the second read is exactly as parallel as the
first. The scatter "write each supertile's live blocks to its slot
range" is realized as the equivalent aligned *gather* (each slot window
pulls its source blocks via the inverted slot map), because Pallas
output windows are shape-aligned while live-run offsets are not.

Still ≤ 2 launches; the stream is bitwise-identical to
``zebra_pack(*zebra_mask(x))`` (live blocks are untouched by masking,
so packing *raw* live blocks is already packing masked ones, and the
zero tail is written explicitly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import cdiv
from .platform import pallas_interpret, tpu_forms
from .schedule import slot_map
from .supertile import comparator_tiles, pack_window
from .zebra_mask import threshold, tile_bitmap, tile_blockmax


def _bitmap_kernel(x_ref, bm_ref, *, thr: float, bs: int, bc: int):
    bm_ref[0, 0] = (tile_blockmax(x_ref[...], bs, bc) >= thr).astype(jnp.int32)


def _gather_pack_kernel(src_ref, nl_ref, *refs, window: int):
    del src_ref                          # consumed by the BlockSpec index maps
    x_refs, out_ref = refs[:window], refs[window]
    s = pl.program_id(0)
    n_live = nl_ref[0]
    parts = []
    for w in range(window):
        blk = x_refs[w][...]                                      # (bs, bc)
        live = (s * window + w) < n_live
        parts.append(jnp.where(live, blk, jnp.zeros_like(blk))[None])
    out_ref[...] = parts[0] if window == 1 else jnp.concatenate(parts, 0)


@functools.partial(jax.jit, static_argnames=("t_obj", "bs", "bc", "tm", "tk",
                                             "window", "gather_kernel"))
def zebra_mask_pack(x: jax.Array, *, t_obj: float, bs: int = 8, bc: int = 128,
                    tm: int | None = None, tk: int | None = None,
                    window: int | None = None,
                    gather_kernel: bool | None = None
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Two-phase comparator + compaction over an (M, K) map.

    Returns ``(payload (n_blocks, bs, bc) — live blocks first in the
    consumer order of kernels.schedule (column-grouped), zero tail;
    bitmap (M//bs, K//bc) int8; n_live () int32)``.
    Bitwise-identical to ``zebra_pack(*zebra_mask(x))`` in ≤ 2 launches.

    ``tm``/``tk`` size the comparator pass's supertile (defaults to the
    module budget chooser); ``window`` is the pack pass's payload slots
    per grid step (defaults to the largest divisor of the block count
    under the cap).
    """
    M, K = x.shape
    if M % bs or K % bc:
        raise ValueError(f"(M={M}, K={K}) must divide by block ({bs},{bc})")
    nm, nk = M // bs, K // bc
    nb = nm * nk
    item = jnp.dtype(x.dtype).itemsize
    # standalone calls take the default-budget choosers; the engine passes
    # ZebraConfig-budgeted tiles and pack window explicitly (same formulas)
    dtm, dtk = comparator_tiles(M, K, bs, bc, item)
    tm, tk = tm or dtm, tk or dtk
    if tm % bs or tk % bc:
        raise ValueError(f"tile ({tm},{tk}) must divide by block ({bs},{bc})")
    W = window or pack_window(nb, bs, bc, item)
    if nb % W:
        raise ValueError(f"pack window {W} must divide n_blocks {nb}")
    if gather_kernel is None:
        gather_kernel = tpu_forms(bs, bc)

    # -- phase 1: parallel comparator, bitmap only --------------------------
    GM, GK = cdiv(M, tm), cdiv(K, tk)
    bm4 = pl.pallas_call(
        functools.partial(_bitmap_kernel, thr=threshold(t_obj, x.dtype),
                          bs=bs, bc=bc),
        grid=(GM, GK),
        in_specs=[pl.BlockSpec((tm, tk), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1, 1, tm // bs, tk // bc),
                               lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((GM, GK, tm // bs, tk // bc),
                                       jnp.int32),
        interpret=pallas_interpret(),
        name="zebra_mask_pack",
    )(x)
    bitmap = tile_bitmap(bm4, nm, nk)

    # -- phase 2a: ONE exclusive scan = counts, offsets and slot map --------
    # the consumer-order slot map (kernels.schedule): column-grouped, so
    # the downstream GEMM reads each K column as one contiguous slot run
    keep, dmap = slot_map(bitmap)
    n_live = jnp.sum(keep).astype(jnp.int32)
    g = jnp.arange(nb, dtype=jnp.int32)
    # invert: src[slot] = block index of the slot's live block (0 for tail,
    # which the pack kernel zeroes via slot >= n_live)
    src = jnp.zeros((nb,), jnp.int32).at[
        jnp.where(keep != 0, dmap, nb)].set(g, mode="drop")

    # -- phase 2b: parallel gather-pack over payload slot windows -----------
    if not gather_kernel:
        # XLA form: the identical gather as one XLA two-index take
        # straight off the 4-D block view — no transposed block copy of
        # the whole map on the producer hot path
        x4 = x.reshape(nm, bs, nk, bc)
        payload = jnp.where((g < n_live)[:, None, None],
                            x4[src // nk, :, src % nk, :],
                            jnp.zeros((), x.dtype))
        return payload, bitmap, n_live

    def _src_idx(s, src, nl, *, w):
        gidx = src[s * W + w]
        return (gidx // nk, gidx % nk)

    payload = pl.pallas_call(
        functools.partial(_gather_pack_kernel, window=W),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb // W,),
            in_specs=[pl.BlockSpec((bs, bc), functools.partial(_src_idx, w=w))
                      for w in range(W)],
            out_specs=pl.BlockSpec((W, bs, bc), lambda s, src, nl: (s, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nb, bs, bc), x.dtype),
        interpret=pallas_interpret(),
        name="zebra_mask_pack",
    )(src, n_live[None], *([x] * W))
    return payload, bitmap, n_live
