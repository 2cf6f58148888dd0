"""The static prefetch schedule of the Zebra consumers — consumer-order
slot maps built ONCE from the bitmap's prefix sums, shared by the
producer (payload emission order), the expander and both GEMM consumers.

Payload order contract (the "GEMM-consumable supertile order"): payload
slots are grouped by K-block **column**, columns ascending, live blocks
ascending by block row within each column, all live slots contiguous in
``[0, n_live)``, zero tail after. Formally, with ``keep`` the (nm, nk)
bitmap::

    counts[k]  = sum_r keep[r, k]            live blocks in column k
    offsets[k] = sum_{k' < k} counts[k']     column k's first payload slot
    slot[r, k] = offsets[k] + |{r' < r : keep[r', k]}|

Why this order wins: a GEMM consumer walks the K dimension column by
column — every ``(bs, bc)`` block in payload column-run ``k`` multiplies
the SAME ``(bc, N)`` weight panel ``w[k*bc:(k+1)*bc]``. Column-grouped
slots make each column's operand one contiguous payload range
(``offsets[k] : offsets[k] + counts[k]``), so the hot path does **zero
dynamic-window gathers**: the fetch plan below (``rows``) is computed
once from the prefix sums before the GEMM, not per supertile step. The
old row-major live-first order forced the consumer to re-derive a
revolving-door fetch window per (supertile, K-step) — that per-step
address generation is exactly what cost more than the skipped FLOPs
(``speedup_vs_ref 0.14`` in the pre-fix trajectory).

``stream_bytes`` is unchanged by the reorder: the stream length depends
only on ``n_live`` (payload slots) + the 1-bit/block index, never on
slot order — pinned by tests/test_mask_pack.py.

Scheduled consume (the XLA realization of the consumer
contract): per column the live blocks are compacted to a static
**capacity** ``cap >= max(counts)`` chosen from the cached autotuning
chooser's ladder (``kernels.supertile.gemm_plan``), giving a dense
``(nk, cap*bs, bc) x (nk, bc, N)`` batched GEMM over ~``n_live/ (nk *
cap)`` of the dense work; the output rows are assembled with a one-hot
**selection matmul** instead of a scatter-add (XLA CPU scatters run at
~4 GB/s; the equivalent tiny GEMM is ~2x faster). The runtime capacity
picks a ladder branch via ``lax.switch`` — only the selected branch
executes. Both consumers feed the literal same ``_consume_at_cap`` with
identical gated operands, so ``zebra_spmm == zebra_spmm_cs`` stays
bitwise by construction.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class PrefetchSchedule(NamedTuple):
    """The static prefetch schedule: every array is a pure function of the
    bitmap's prefix sums, built once per consumer call (and CSE'd with
    the producer's identical scan when both live in one jit).

    keep     (nm, nk) int32 keep flags
    counts   (nk,)    live blocks per K-block column
    offsets  (nk,)    exclusive prefix sum of counts — column k's slot run
                      starts at offsets[k]
    slot     (nm, nk) block -> payload slot (consumer order)
    rows     (nk, nm) fetch plan: rows[k, i] = block row of the i-th live
                      block in column k; ``nm`` pads past counts[k]
    """
    keep: jax.Array
    counts: jax.Array
    offsets: jax.Array
    slot: jax.Array
    rows: jax.Array


def consumer_schedule(bitmap: jax.Array) -> PrefetchSchedule:
    """Build the static prefetch schedule from the bitmap prefix sums."""
    nm, nk = bitmap.shape
    keep = bitmap.astype(jnp.int32)
    counts = keep.sum(axis=0)
    offsets = (jnp.cumsum(counts) - counts).astype(jnp.int32)
    colrank = (jnp.cumsum(keep, axis=0) - keep).astype(jnp.int32)
    slot = offsets[None, :] + colrank
    kk = jnp.broadcast_to(jnp.arange(nk, dtype=jnp.int32)[None, :], (nm, nk))
    rr = jnp.broadcast_to(jnp.arange(nm, dtype=jnp.int32)[:, None], (nm, nk))
    # scatter each live block's row into its column rank; dead blocks aim
    # at column nm and are dropped — the pad value stays nm
    ctgt = jnp.where(keep != 0, colrank, nm)
    rows = jnp.full((nk, nm), nm, jnp.int32).at[
        kk.reshape(-1), ctgt.reshape(-1)].set(rr.reshape(-1), mode="drop")
    return PrefetchSchedule(keep=keep, counts=counts.astype(jnp.int32),
                            offsets=offsets, slot=slot, rows=rows)


def slot_map(bitmap: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Flat (row-major block index g = r*nk + k) keep flags and the
    consumer-order block -> payload-slot map — the address form the
    Pallas kernel realizations scalar-prefetch (pack / unpack /
    payload-window GEMM all index their windows through this ONE map).

    A dead block's slot aliases the next live slot of its column (its
    exclusive column rank), which keeps the TPU pack kernel's
    "live write wins" revolving-door rule intact under the k-outer grid
    order and keeps every value <= n_live <= nb - 1 whenever a dead
    block exists."""
    sched = consumer_schedule(bitmap)
    return (sched.keep.reshape(-1), sched.slot.reshape(-1).astype(jnp.int32))


# ---------------------------------------------------------------------------
# Scheduled consume — the XLA realization of the consumer contract
# ---------------------------------------------------------------------------

def _consume_at_cap(A: jax.Array, rows_c: jax.Array, w: jax.Array,
                    nm: int, bs: int) -> jax.Array:
    """THE scheduled GEMM core shared by both consumers: A (nk, cap, bs,
    bc) is the compacted, keep-gated operand (invalid slots exact +0);
    rows_c (nk, cap) its fetch plan (pad nm). Batched per-column panel
    GEMM, then one-hot selection-matmul assembly of the output rows."""
    nk, cap, _, bc = A.shape
    N = w.shape[1]
    part = jax.lax.dot_general(
        A.reshape(nk, cap * bs, bc), w.reshape(nk, bc, N),
        (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32)
    # selection matmul: P[s, r] = 1 iff compacted slot s holds block row r;
    # pad rows target column nm of the (nm + 1)-wide one-hot and are
    # sliced away — no scatter-add on the hot path
    P = jnp.zeros((nk * cap, nm + 1), jnp.float32).at[
        jnp.arange(nk * cap), rows_c.reshape(-1)].set(1.0, mode="drop")
    y = jax.lax.dot_general(P[:, :nm], part.reshape(nk * cap, bs * N),
                            (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return y.reshape(nm * bs, N)


def _gather_from_x(x: jax.Array, sched: PrefetchSchedule, cap: int,
                   nm: int, nk: int, bs: int, bc: int) -> tuple:
    """Compact the live blocks straight from the dense operand: only the
    fetch plan's live block rows are ever read, so dead-block values
    (raw, unmasked x) cannot leak."""
    rows_c = sched.rows[:, :cap]
    valid = rows_c < nm
    rsafe = jnp.where(valid, rows_c, 0)
    x4 = x.reshape(nm, bs, nk, bc)
    kcol = jnp.arange(nk, dtype=jnp.int32)[:, None]
    A = x4[rsafe, :, kcol, :]                        # (nk, cap, bs, bc)
    A = jnp.where(valid[:, :, None, None], A, jnp.zeros((), x.dtype))
    return A, rows_c


def _gather_from_payload(payload: jax.Array, sched: PrefetchSchedule,
                         cap: int, nm: int, nk: int) -> tuple:
    """Compact from the consumer-ordered payload: column k's operand is
    the contiguous slot run offsets[k] : offsets[k] + counts[k] — the
    zero-dynamic-gather property the payload order exists for."""
    rows_c = sched.rows[:, :cap]
    valid = rows_c < nm
    slots = sched.offsets[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
    A = payload[jnp.where(valid, slots, 0)]          # (nk, cap, bs, bc)
    A = jnp.where(valid[:, :, None, None], A, jnp.zeros((), payload.dtype))
    return A, rows_c


def scheduled_consume(operand: jax.Array, w: jax.Array,
                      sched: PrefetchSchedule, caps: tuple[int, ...], *,
                      from_payload: bool, nm: int, nk: int, bs: int, bc: int
                      ) -> jax.Array:
    """Run the scheduled GEMM at the smallest ladder capacity covering
    ``max(counts)`` — a ``lax.switch`` over the chooser's capacity
    ladder; XLA executes only the selected branch, so the work scales
    with the realized sparsity while shapes stay static."""
    caps = tuple(min(int(c), nm) for c in caps)
    if not caps or caps[-1] != nm:
        caps = tuple(c for c in caps if c < nm) + (nm,)

    gather = (_gather_from_payload if from_payload else
              functools.partial(_gather_from_x, bs=bs, bc=bc))

    def branch(cap: int) -> Callable:
        def run(op, ws, sc):
            A, rows_c = gather(op, sc, cap, nm, nk)
            return _consume_at_cap(A, rows_c, ws, nm, bs)
        return run

    if len(caps) == 1:
        return branch(caps[0])(operand, w, sched)
    idx = jnp.searchsorted(jnp.asarray(caps, jnp.int32),
                           jnp.max(sched.counts))
    return jax.lax.switch(idx, [branch(c) for c in caps],
                          operand, w, sched)
