"""Pallas TPU kernel: supertiled GEMM over the *compressed* Zebra stream.

``zebra_spmm_cs`` computes ``y = mask(x) @ w`` from the ``(payload,
bitmap)`` stream that ``zebra_mask_pack`` produced. The payload follows
the consumer order of ``kernels.schedule`` (column-grouped), so each K
column's operand is ONE contiguous slot run — no dynamic-window gathers
on the hot path. The consumer has three executable realizations of the
one contract:

* **scheduled form** (``scheduled=True``; the default off a TPU or for
  blocks narrower than a vreg tile): the static prefetch schedule slices each
  column's contiguous slot run at a ladder capacity from the cached
  ``supertile.gemm_plan`` chooser and runs the batched panel GEMM +
  selection-matmul assembly of ``kernels.schedule`` — the realization
  that beats the dense matmul at the paper's operating point. It is
  bitwise-equal to ``zebra_spmm``'s scheduled form by construction:
  both feed the literal same ``_consume_at_cap`` with identical gated
  operands (live block values are untouched by masking, so compacting
  from the payload and from the dense map give the same arrays).
* **TPU form** (``payload_windows=True``; default on a TPU,
  ``kernels.platform.tpu_forms``): the grid steps over ``(stm, stk)`` supertiles
  and every ``(bs, bc)`` block of the supertile is fetched straight
  from its consumer-order payload slot through its own
  scalar-prefetch-indexed BlockSpec — ``R·C`` windows per step. A dead
  block's window replays the prefix-sum slot (the in-bounds
  revolving-door re-use) and is zero-gated in-kernel. Accumulation
  order, supertile shapes and the in-kernel panel assembly are
  *identical* to ``zebra_spmm``'s kernel form (shared
  ``gemm_supertile_body``), so the two kernel forms are bitwise-equal.
* **expand form** (``scheduled=False, payload_windows=False``): the
  slot map drives one XLA blocked gather that expands the payload back
  to the dense operand, which then feeds the same supertiled Pallas
  GEMM — kept as the bitwise cross-check of the TPU form on CPU.
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
from .zebra_spmm import (gemm_supertile_body, launch_supertile_gemm,
                         seg_live)


def _spmm_cs_kernel(smap_ref, keep_ref, seg_ref, *refs, R: int, C: int,
                    bs: int, bc: int, nk: int, GK: int):
    """Payload-window flavor: blocks come from the R*C dynamically
    slotted payload windows; the step itself IS gemm_supertile_body, so
    the bitwise parity with zebra_spmm is structural, not copy-pasted."""
    del smap_ref                        # consumed by the BlockSpec index maps
    p_refs, w_ref, y_ref, acc_ref = \
        refs[:R * C], refs[R * C], refs[R * C + 1], refs[R * C + 2]
    gemm_supertile_body(
        keep_ref, seg_ref,
        lambda r, j: p_refs[r * C + j][...][0],
        w_ref, y_ref, acc_ref, R=R, C=C, bc=bc, nk=nk, GK=GK)


def _payload_window_launch(payload, w, keep, smap, *, bs, bc, stm, stk, bn,
                           nm, nk):
    """The payload-direct TPU form: R*C dynamically-slotted payload
    windows per supertile step."""
    K, N = w.shape
    R, C = stm // bs, stk // bc
    GM, GN, GK = nm // R, cdiv(N, bn), nk // C
    # only seg: the payload form addresses its fetches through smap, so
    # the dense form's revolving-door kmap would be computed then thrown
    # away here
    seg = seg_live(keep, nm, nk, R, C).reshape(-1).astype(jnp.int32)

    def _p_idx(i, jn, kc, smap, keep, seg, *, r, j):
        return (smap[(i * R + r) * nk + kc * C + j], 0, 0)

    kernel = functools.partial(_spmm_cs_kernel, R=R, C=C, bs=bs, bc=bc,
                               nk=nk, GK=GK)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(GM, GN, GK),
            in_specs=[pl.BlockSpec((1, bs, bc),
                                   functools.partial(_p_idx, r=r, j=j))
                      for r in range(R) for j in range(C)] +
                     [pl.BlockSpec((stk, bn),
                                   lambda i, jn, kc, smap, keep, seg:
                                   (kc, jn))],
            out_specs=pl.BlockSpec(
                (stm, bn), lambda i, jn, kc, smap, keep, seg: (i, jn)),
            scratch_shapes=[pltpu.VMEM((stm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nm * bs, N), jnp.float32),
        interpret=pallas_interpret(),
        name="zebra_spmm_cs",
    )(smap, keep, seg, *([payload] * (R * C)), w)


@functools.partial(jax.jit, static_argnames=("bs", "bc", "bn", "stm", "stk",
                                             "caps", "zero_frac_hint",
                                             "scheduled", "payload_windows"))
def zebra_spmm_cs(payload: jax.Array, w: jax.Array, bitmap: jax.Array, *,
                  bs: int = 8, bc: int = 128, bn: int | None = None,
                  stm: int | None = None, stk: int | None = None,
                  caps: tuple[int, ...] | None = None,
                  zero_frac_hint: float | None = None,
                  scheduled: bool | None = None,
                  payload_windows: bool | None = None) -> jax.Array:
    """(n_blocks, bs, bc) payload x (K, N) weight -> (M, N) fp32.

    ``bitmap`` is the (M//bs, K//bc) keep map; payload slots follow the
    consumer order of ``kernels.schedule`` (``zebra_mask_pack``'s
    emission order). Plans default from the same cached chooser as
    ``zebra_spmm`` — the two must tile alike for their bitwise parity
    to hold. ``scheduled=None`` picks the scheduled XLA form unless
    ``platform.tpu_forms(bs, bc)``; ``payload_windows`` selects between the two Pallas
    kernel-form realizations when ``scheduled`` is off.
    """
    nm, nk = bitmap.shape
    K, N = w.shape
    if K != nk * bc:
        raise ValueError(f"w rows {K} != bitmap cols {nk} * bc {bc}")
    if payload.shape != (nm * nk, bs, bc):
        raise ValueError(f"payload {payload.shape} != ({nm * nk}, {bs}, {bc})")
    M = nm * bs
    plan = gemm_plan(M, K, N, bs, bc, jnp.dtype(payload.dtype).itemsize,
                     zero_frac=zero_frac_hint)
    stm, stk, bn = stm or plan.stm, stk or plan.stk, min(bn or plan.bn, N)
    validate_supertile(M, K, bs, bc, stm, stk)
    kernel_forms = tpu_forms(bs, bc)
    if scheduled is None:
        # explicit payload_windows (either value) asks for a kernel-form
        # realization; otherwise the scheduled XLA form runs wherever the
        # TPU forms do not
        scheduled = not kernel_forms and payload_windows is None
    if scheduled:
        sched = consumer_schedule(bitmap)
        return scheduled_consume(payload, w, sched, caps or plan.caps,
                                 from_payload=True, nm=nm, nk=nk,
                                 bs=bs, bc=bc)
    if payload_windows is None:
        payload_windows = kernel_forms
    sched = consumer_schedule(bitmap)
    keep = sched.keep.reshape(-1)
    smap = sched.slot.reshape(-1).astype(jnp.int32)      # block -> slot

    if payload_windows:
        return _payload_window_launch(payload, w, keep, smap, bs=bs, bc=bc,
                                      stm=stm, stk=stk, bn=bn, nm=nm, nk=nk)

    # expand form: one XLA blocked gather (pack.expand_payload, shared
    # with zebra_unpack) expands the stream back to the dense operand;
    # the supertiled GEMM kernel (shared with zebra_spmm) re-gates every
    # block by keep, so slot-replayed blocks never leak.
    from .pack import expand_payload
    x2 = expand_payload(payload, keep, smap, nm, nk, bs, bc)
    return launch_supertile_gemm(x2, w, keep, bs=bs, bc=bc, stm=stm, stk=stk,
                                 bn=bn, name="zebra_spmm_cs")
