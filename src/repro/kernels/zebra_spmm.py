"""Pallas TPU kernel: supertiled block-sparse activation x dense weight GEMM
that *skips* Zebra zero blocks — harvesting the bandwidth sparsity as MXU
time (beyond-paper; the paper's ASIC gets the skip for free, DESIGN.md §7).

    y[M, N] = (x ⊙ blockmask)[M, K] @ w[K, N]

Grid coarseness is the whole game: the old kernel stepped one ``(bs, bc)``
Zebra block per grid step (grid ``(M/bs, N/bn, K/bc)``), paying the
per-step machinery once per block. This version steps one **supertile** —
an ``(stm, stk) = (R·bs, C·bc)`` multi-block window chosen by
``ZebraConfig.tiles_for(..., kind="gemm")`` under ``vmem_budget_bytes`` —
so the grid shrinks by the supertile factor ``R·C`` while each step makes
``C`` MXU-shaped dot calls over ``(stm, bc)`` column panels.

Skip machinery, now at two granularities:

* **supertile**: a per-supertile any-live flag rides in scalar-prefetch
  SMEM; a fully dead supertile skips all of its dots in ONE ``pl.when``
  (dead work dropped in coarse chunks), and the x-window index map
  replays the last any-live supertile column (revolving-door), so the
  pruned supertile is never fetched from HBM;
* **block**: within a live supertile, each ``(bs, bc)`` block is gated by
  its keep flag (``jnp.where`` to exact +0) before entering the column
  panel — dead blocks contribute exact zeros whatever the raw ``x``
  holds, and the panel assembly is *identical code* to the
  compressed-stream consumer (``zebra_spmm_cs``), which is what makes
  the two bitwise-equal.

Accumulation: fp32 VMEM scratch, K innermost, ``C`` sequential panel
dots per step in ascending K order — the same per-row summation order
for every legal supertile choice, so retiling does not move the result.

Two executable realizations of the one contract, selected by
``scheduled`` (default: the scheduled XLA form wherever the TPU form
does not run — ``kernels.platform.tpu_forms``):

* **scheduled form** (CPU containers / XLA): the static prefetch
  schedule of ``kernels.schedule`` compacts each K column's live blocks
  to a ladder capacity from the cached ``supertile.gemm_plan`` chooser
  and runs one batched panel GEMM + selection-matmul assembly — the
  realization that actually beats the dense matmul at the paper's
  operating point (BENCH_kernels.json ``speedup_vs_dense``). Bitwise
  equal to ``zebra_spmm_cs``'s scheduled form by construction (same
  ``_consume_at_cap``, identical gated operands).
* **kernel form** (``scheduled=False``, the TPU form): the supertiled
  Pallas GEMM below, bitwise-equal to ``zebra_spmm_cs``'s
  payload-window form via the shared ``gemm_supertile_body``.

The two forms sum partial products in different orders (sequential
panel accumulate vs batched GEMM + selection matmul), so cross-form
parity is allclose-tight, not bitwise; *within* each form the dense and
compressed consumers are bitwise-equal, which is the contract the
acceptance tests pin.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import cdiv
from .platform import pallas_interpret, tpu_forms
from .schedule import consumer_schedule, scheduled_consume
from .supertile import gemm_plan, validate_supertile


def gemm_supertile_body(keep_ref, seg_ref, get_block, w_ref, y_ref, acc_ref,
                        *, R: int, C: int, bc: int, nk: int, GK: int):
    """THE supertile GEMM step, shared by the dense and compressed
    consumers — their bitwise parity rests on this body being literally
    the same code, with only the block accessor differing.

    One (stm, bn) output window: accumulate C column-panel dots of the
    (stm, stk) activation supertile in ascending K order, gating each
    (bs, bc) block by its keep flag (exact +0 for dead blocks, whatever
    the fetched window holds). A fully dead supertile skips all C dots
    in one pl.when. ``get_block(r, j)`` returns the (bs, bc) block of
    the supertile's r-th block row / j-th block column."""
    i, kc = pl.program_id(0), pl.program_id(2)

    @pl.when(kc == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(seg_ref[i * GK + kc] != 0)
    def _acc():
        ws = w_ref[...]
        for j in range(C):
            cols = []
            for r in range(R):
                live = keep_ref[(i * R + r) * nk + kc * C + j] != 0
                blk = get_block(r, j)
                cols.append(jnp.where(live, blk, jnp.zeros_like(blk)))
            xj = cols[0] if R == 1 else jnp.concatenate(cols, 0)
            acc_ref[...] += jnp.dot(xj, ws[j * bc:(j + 1) * bc, :],
                                    preferred_element_type=jnp.float32)

    @pl.when(kc == GK - 1)
    def _flush():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def _dense_gemm_kernel(keep_ref, seg_ref, kmap_ref, x_ref, w_ref, y_ref,
                       acc_ref, *, R: int, C: int, bs: int, bc: int,
                       nk: int, GK: int):
    """Dense-operand flavor: blocks come from the (stm, stk) x window.
    ``kmap_ref`` (the revolving-door fetch map) is consumed by the
    BlockSpec index maps, not the body."""
    del kmap_ref
    gemm_supertile_body(
        keep_ref, seg_ref,
        lambda r, j: x_ref[r * bs:(r + 1) * bs, j * bc:(j + 1) * bc],
        w_ref, y_ref, acc_ref, R=R, C=C, bc=bc, nk=nk, GK=GK)


def seg_live(keep: jax.Array, nm: int, nk: int, R: int, C: int) -> jax.Array:
    """Per-supertile any-live flags, (GM, GK) shaped."""
    GM, GK = nm // R, nk // C
    return keep.reshape(GM, R, GK, C).sum(axis=(1, 3)) > 0


def seg_live_and_kmap(keep: jax.Array, nm: int, nk: int, R: int, C: int
                      ) -> tuple[jax.Array, jax.Array]:
    """Per-supertile any-live flags (GM*GK,) and the revolving-door map:
    for each (supertile row, supertile col), the last any-live supertile
    column <= it (or 0), so a dead supertile's fetch is a VMEM re-use."""
    seg = seg_live(keep, nm, nk, R, C)
    GK = seg.shape[1]
    idx = jnp.arange(GK, dtype=jnp.int32)[None, :] * seg
    kmap = jax.lax.associative_scan(jnp.maximum, idx, axis=1)
    return seg.reshape(-1).astype(jnp.int32), kmap.reshape(-1).astype(jnp.int32)


def launch_supertile_gemm(x2: jax.Array, w: jax.Array, keep: jax.Array, *,
                          bs: int, bc: int, stm: int, stk: int, bn: int,
                          name: str = "zebra_spmm") -> jax.Array:
    """Launch the supertiled GEMM over a dense (M, K) activation operand
    (raw or blocked-expanded — dead blocks are keep-gated in-kernel).
    ``name`` is the kernel's name in the compiled program and its trace."""
    M, K = x2.shape
    N = w.shape[1]
    nm, nk = M // bs, K // bc
    R, C = stm // bs, stk // bc
    GM, GN, GK = nm // R, cdiv(N, bn), nk // C
    seg, kmap = seg_live_and_kmap(keep, nm, nk, R, C)
    kernel = functools.partial(_dense_gemm_kernel, R=R, C=C, bs=bs, bc=bc,
                               nk=nk, GK=GK)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(GM, GN, GK),
            in_specs=[
                pl.BlockSpec((stm, stk),
                             lambda i, jn, kc, keep, seg, kmap:
                             (i, kmap[i * GK + kc])),
                pl.BlockSpec((stk, bn),
                             lambda i, jn, kc, keep, seg, kmap: (kc, jn)),
            ],
            out_specs=pl.BlockSpec(
                (stm, bn), lambda i, jn, kc, keep, seg, kmap: (i, jn)),
            scratch_shapes=[pltpu.VMEM((stm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=pallas_interpret(),
        name=name,
    )(keep, seg, kmap, x2, w)


@functools.partial(jax.jit, static_argnames=("bs", "bc", "bn", "stm", "stk",
                                             "caps", "zero_frac_hint",
                                             "scheduled"))
def zebra_spmm(x: jax.Array, w: jax.Array, bitmap: jax.Array, *,
               bs: int = 8, bc: int = 128, bn: int | None = None,
               stm: int | None = None, stk: int | None = None,
               caps: tuple[int, ...] | None = None,
               zero_frac_hint: float | None = None,
               scheduled: bool | None = None) -> jax.Array:
    """(M,K) x (K,N) with (M//bs, K//bc) keep-bitmap -> (M,N) fp32.

    ``stm``/``stk``/``bn`` size the kernel-form GEMM supertile and
    ``caps`` the scheduled form's capacity ladder — both default from
    the cached ``supertile.gemm_plan`` chooser (``zero_frac_hint``
    tightens the ladder; the engine threads its config hint through).
    ``scheduled=None`` picks the scheduled XLA form unless
    ``platform.tpu_forms(bs, bc)``."""
    M, K = x.shape
    K2, N = w.shape
    assert K2 == K and bitmap.shape == (M // bs, K // bc), (bitmap.shape, M, K)
    plan = gemm_plan(M, K, N, bs, bc, jnp.dtype(x.dtype).itemsize,
                     zero_frac=zero_frac_hint)
    stm, stk, bn = stm or plan.stm, stk or plan.stk, min(bn or plan.bn, N)
    validate_supertile(M, K, bs, bc, stm, stk)
    if scheduled is None:
        scheduled = not tpu_forms(bs, bc)
    if scheduled:
        sched = consumer_schedule(bitmap)
        return scheduled_consume(x, w, sched, caps or plan.caps,
                                 from_payload=False, nm=M // bs, nk=K // bc,
                                 bs=bs, bc=bc)
    keep = bitmap.reshape(-1).astype(jnp.int32)
    return launch_supertile_gemm(x, w, keep, bs=bs, bc=bc, stm=stm, stk=stk,
                                 bn=bn)
