"""Shard-aware compressed collectives: move the Zebra (bitmap, payload)
stream across mesh axes instead of dense tensors.

The paper's argument is about a bandwidth wall, not about DRAM
specifically — the blocks that are zero in HBM are zero on the wire, so
at multi-device scale the interconnect (ICI/DCN) is the same boundary
Eq. 2/3 attacks. Every collective here follows one wire protocol:

1. **Index exchange** — one ``lax.all_gather`` of the tiny ``(nm, nk)``
   keep bitmaps (the Eq. 3 term: 1 bit/block on the physical wire; the
   host-mesh realization moves int8 flags, the accounting charges the
   packed form every other transport in the repo charges).
2. **Payload exchange** — ``n - 1`` ring hops of ``lax.ppermute`` over
   the payload buffer. Per hop, each inbound link carries ONE shard's
   compressed stream; over the full ring every device's link carries
   every other shard's stream exactly once.
3. **Reconstruction** — each arriving shard's dense map is rebuilt from
   ITS bitmap via the consumer-order slot map (``kernels/schedule.py``'s
   prefix-sum pass — the same ONE slot map the Pallas kernels
   scalar-prefetch), so the gather is bitwise-equal to ``lax.all_gather``
   of the dense masked map.

Accounting follows the repo's HBM precedent (``CompressedMap``): the
*physically moved* buffer is worst-case sized (ring hops need static
shapes), but the *accounted* bytes are the live stream — payload slots
that would cross a real link plus the packed index — via the same
``core.engine.stream_bytes`` rule every compressed backend uses, so HBM
and ICI byte models cannot drift apart. ``LinkBytes`` carries the pair
(moved, dense-equivalent) per inbound link; ``compress/meter.py``'s
``record_link`` reconciles it against Eq. 2/3 exactly.

Degrade contract mirrors ``core.engine``: a layer exchange runs
compressed only when the site's backend declares the ``comms``
capability (``core.backends``) AND the axis/shape situation supports it
(:func:`resolve_comms`); otherwise it falls back to a dense
``lax.all_gather`` with the reason logged once and surfaced on the
``SiteAux`` backend label — never a silent rewrite.

Everything here must run inside ``shard_map`` over a mesh with the
target axis; :func:`shard_map_compat` papers over the jax version drift
(``jax.shard_map``/``check_vma`` vs ``jax.experimental.shard_map``/
``check_rep``). Model code never calls these directly — it goes through
``distributed.ctx.comm_context`` + the layer hooks in ``models/lm``.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..compress.stream import nonzero_bitmap
from ..core.engine import MB_BASE, SiteAux, stream_bytes
from ..kernels.ref import zebra_unpack_ref
from ..kernels.schedule import slot_map
from .ctx import comm_axis

_log = logging.getLogger("repro.collectives")
_DEGRADE_LOGGED: set[tuple[str, str, str]] = set()

RING_SITE = "ring"   # ft.breaker site label for the collectives hop boundary


# ---------------------------------------------------------------------------
# shard_map / axis size
# ---------------------------------------------------------------------------

def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replica checking off — the collectives here
    use ``lax.axis_index``, which is per-shard by construction."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def axis_size(axis) -> int:
    """Static shard count of a mesh axis inside shard_map (``lax.psum``
    of a Python scalar constant-folds to a Python int at trace time)."""
    return int(lax.psum(1, axis))


# ---------------------------------------------------------------------------
# Per-link byte accounting
# ---------------------------------------------------------------------------

class LinkBytes(NamedTuple):
    """Bytes ONE inbound link of this device carried for one collective.

    ``moved``  what actually crossed: compressed stream bytes on the
               compressed path, the dense size on a degraded exchange.
    ``dense``  dense-equivalent bytes the same exchange would move with
               ``lax.all_gather``/psum of the uncompressed map.
    Both int32 (per-exchange counts; cross-layer accumulation rides the
    exact ``LayerAux`` (hi, lo) pair)."""
    moved: jax.Array
    dense: jax.Array


def zero_link() -> LinkBytes:
    return LinkBytes(jnp.int32(0), jnp.int32(0))


def add_links(a: LinkBytes, b: LinkBytes) -> LinkBytes:
    return LinkBytes(a.moved + b.moved, a.dense + b.dense)


def attach_link(aux: SiteAux, link: LinkBytes, *,
                reason: str | None = None) -> SiteAux:
    """Fold one exchange's per-link bytes into a ``SiteAux``. A degraded
    (dense) exchange surfaces its reason on the backend label —
    ``"<backend>+dense-comms(<reason>)"`` — following the engine's
    ``reference(<reason>)`` convention."""
    label = (aux.backend if reason is None
             else f"{aux.backend}+dense-comms({reason})")
    return dataclasses.replace(
        aux,
        ici_bytes=jnp.asarray(aux.ici_bytes).astype(jnp.int32) + link.moved,
        ici_dense_bytes=(jnp.asarray(aux.ici_dense_bytes).astype(jnp.int32)
                         + link.dense),
        backend=label)


def dense_link(nbytes_per_shard, n: int) -> LinkBytes:
    """The LinkBytes of a degraded (dense) all-gather: every inbound link
    carries the other ``n - 1`` shards' dense maps."""
    b = jnp.int32((n - 1) * int(nbytes_per_shard))
    return LinkBytes(b, b)


# ---------------------------------------------------------------------------
# Payload pack (the jnp realization of the consumer-order contract)
# ---------------------------------------------------------------------------

def _pack_consumer_order(x2: jax.Array, bitmap: jax.Array, bs: int, bc: int
                         ) -> tuple[jax.Array, jax.Array]:
    """(M, K) map + (nm, nk) bitmap -> worst-case (nb, bs, bc) payload in
    the repo-wide consumer slot order, plus n_live. Slots come from the
    SAME ``kernels.schedule.slot_map`` prefix-sum pass the Pallas kernels
    scalar-prefetch; a dead block's slot aliases the next live slot of
    its column, so dead blocks must scatter with ``mode="drop"`` (a
    plain set would overwrite live data)."""
    M, K = x2.shape
    nm, nk = M // bs, K // bc
    nb = nm * nk
    keep, slot = slot_map(bitmap)
    blocks = (x2.reshape(nm, bs, nk, bc).transpose(0, 2, 1, 3)
              .reshape(nb, bs, bc))
    tgt = jnp.where(keep != 0, slot, jnp.int32(nb))      # dead -> dropped
    payload = jnp.zeros((nb, bs, bc), x2.dtype).at[tgt].set(
        blocks, mode="drop")
    return payload, jnp.sum(keep).astype(jnp.int32)


# ---------------------------------------------------------------------------
# zebra_all_gather — the compressed TP activation exchange
# ---------------------------------------------------------------------------

def zebra_all_gather(x2: jax.Array, axis, *, bs: int, bc: int,
                     bitmap: jax.Array | None = None, tiled: bool = False,
                     validation: str = "off", live_nonzero: bool = True,
                     site: str = "all_gather"
                     ) -> tuple[jax.Array, LinkBytes]:
    """All-gather a block-sparse (M, K) shard in Zebra stream form.

    Wire protocol: ONE ``lax.all_gather`` of the (nm, nk) bitmaps (the
    index exchange), then ``n - 1`` ring ``ppermute`` hops of the
    consumer-order payload; each arriving shard's dense map is rebuilt
    from its own bitmap's slot map. Bitwise-equal to ``lax.all_gather``
    of the dense map whenever each shard's dead blocks (per its bitmap)
    are exact zeros — always true for the default ``nonzero_bitmap``
    and for any Zebra-masked map under its keep bitmap.

    ``validation`` (a ``compress.integrity`` level) checks every
    arriving hop's stream against its own gathered bitmap (+ its
    producer checksum at the ``checksum`` level) before trusting it. A
    failed hop anywhere on the ring makes EVERY device — the ok flags
    are made uniform with a psum first, collectives inside ``lax.cond``
    require one branch ring-wide — retry the whole exchange as a dense
    ``lax.all_gather`` of the shard still in hand (``ft.faults`` policy
    "recompute-dense" + dense-comms retry), firing
    ``integrity.note_failure`` once per device. The retry traffic is
    accounted on top of the wasted compressed attempt.

    Returns ``(gathered, LinkBytes)``: ``(n, M, K)`` stacked like
    ``lax.all_gather`` (or ``(n*M, K)`` with ``tiled=True``), plus the
    per-inbound-link accounting — over the ring each link carries every
    other shard's stream exactly once::

        moved = sum_{s != self} n_live_s * bs * bc * itemsize
                                + ceil(nm * nk / 8)
        dense = (n - 1) * M * K * itemsize
    """
    from ..compress import integrity
    from ..ft.inject import ring_hop_tap

    M, K = x2.shape
    if M % bs or K % bc:
        raise ValueError(f"zebra_all_gather: shard ({M}, {K}) not divisible "
                         f"by blocks ({bs}, {bc}) — resolve_comms should "
                         f"have degraded this exchange to dense")
    nm, nk = M // bs, K // bc
    if bitmap is None:
        bitmap = nonzero_bitmap(x2, bs, bc)
    n = axis_size(axis)
    item = jnp.dtype(x2.dtype).itemsize
    if n == 1:
        return (x2 if tiled else x2[None]), zero_link()
    tag = f"ring:{site}"

    payload, _ = _pack_consumer_order(x2, bitmap, bs, bc)
    bitmaps = lax.all_gather(bitmap, axis)               # (n, nm, nk)
    counts = bitmaps.astype(jnp.int32).sum(axis=(1, 2))  # per-shard n_live
    csums = None
    if validation == "checksum":
        my_csum = integrity.stream_checksum(payload, bitmap,
                                            counts[lax.axis_index(axis)])
        csums = lax.all_gather(my_csum, axis)            # (n,)
    idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def hop(carry, h):
        # after hop h (1-based), this device holds shard (idx - h) % n
        pl, ok = carry
        pl = lax.ppermute(pl, axis, perm)
        pl = ring_hop_tap(pl, h, site=tag)
        src = (idx - h) % n
        if validation != "off":
            ok = ok & integrity.check_stream(
                pl, bitmaps[src], counts[src], level=validation,
                checksum=None if csums is None else csums[src],
                live_nonzero=live_nonzero)
        return (pl, ok), (zebra_unpack_ref(pl, bitmaps[src], bs, bc), src)

    (_, ok), (shards, srcs) = lax.scan(hop, (payload, jnp.bool_(True)),
                                       jnp.arange(1, n))
    out = jnp.zeros((n, M, K), x2.dtype).at[idx].set(x2)
    out = out.at[srcs].set(shards)

    streams = stream_bytes(counts, bs, bc, x2.dtype, nm * nk)
    moved = (jnp.sum(streams) - streams[idx]).astype(jnp.int32)
    dense = jnp.int32((n - 1) * M * K * item)
    if validation != "off":
        # any corrupt hop anywhere -> the whole ring retries dense
        ok_ring = lax.psum(ok.astype(jnp.int32), axis) == n

        def retry_dense():
            jax.debug.callback(lambda t=tag: integrity.note_failure(t))
            return lax.all_gather(x2, axis)

        out = lax.cond(ok_ring, lambda: out, retry_dense)
        moved = jnp.where(ok_ring, moved, moved + dense)
    return (out.reshape(n * M, K) if tiled else out), LinkBytes(moved, dense)


# ---------------------------------------------------------------------------
# zebra_psum_stream / zebra_reduce_scatter — payload-form reductions
# ---------------------------------------------------------------------------

def zebra_psum_stream(g2: jax.Array, axis, *, bs: int, bc: int,
                      bitmap: jax.Array | None = None,
                      validation: str = "off", site: str = "psum"
                      ) -> tuple[jax.Array, jax.Array, LinkBytes]:
    """psum of hard-masked maps (``g * bitmap`` — the activation-gradient
    form under the hard grad mode) that never densifies mid-flight.

    The index exchange gathers every shard's bitmap; their union sets
    the payload capacity. Each shard packs its map at the UNION layout
    (blocks dead in its own map contribute exact-zero slots), so the
    ``n - 1`` ring hops can add arriving payloads slot-for-slot — the
    reduction stays in payload form and is expanded ONCE at the end.
    Exact whenever each shard's off-bitmap blocks are exact zeros;
    floating-point summation order is the ring order (own shard first),
    which differs from ``lax.psum``'s tree — integer-valued data sums
    bitwise-equal, generic f32 agrees to normal accumulation-order
    tolerance.

    Returns ``(summed dense map, union bitmap, LinkBytes)`` with::

        moved = (n - 1) * (union_live * bs * bc * itemsize
                           + ceil(nm * nk / 8))
        dense = (n - 1) * M * K * itemsize

    (both sides modeled as the same gather-and-reduce ring: full
    buffers circulate, the reduction rides the ring in stream form).

    ``validation`` checks each ARRIVING payload (at hop h the traveling
    buffer is one shard's original union-capacity stream) before it is
    added: finiteness at ``structural``; + the producer's gathered
    checksum at ``checksum`` level — which is the level that sees a
    dropped hop here, since a zeroed union-capacity payload is
    structurally legal (slots live in the union may be zero locally,
    the ``live_nonzero`` invariant does not apply). On any failure the
    whole ring retries as a dense ``lax.psum``."""
    from ..compress import integrity
    from ..ft.inject import ring_hop_tap

    M, K = g2.shape
    if M % bs or K % bc:
        raise ValueError(f"zebra_psum_stream: shard ({M}, {K}) not "
                         f"divisible by blocks ({bs}, {bc})")
    nm, nk = M // bs, K // bc
    if bitmap is None:
        bitmap = nonzero_bitmap(g2, bs, bc)
    n = axis_size(axis)
    item = jnp.dtype(g2.dtype).itemsize
    if n == 1:
        return g2, bitmap.astype(jnp.int8), zero_link()
    tag = f"ring:{site}"

    bitmaps = lax.all_gather(bitmap, axis)               # (n, nm, nk)
    union = (bitmaps.astype(jnp.int32).sum(axis=0) > 0).astype(jnp.int8)
    payload, _ = _pack_consumer_order(g2, union, bs, bc)
    u_live = jnp.sum(union.astype(jnp.int32))
    idx = lax.axis_index(axis)
    csums = None
    if validation == "checksum":
        csums = lax.all_gather(
            integrity.stream_checksum(payload, union, u_live), axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def hop(carry, h):
        pl, acc, ok = carry
        pl = lax.ppermute(pl, axis, perm)
        pl = ring_hop_tap(pl, h, site=tag)
        if validation != "off":
            ok = ok & integrity.check_stream(
                pl, union, u_live, level=validation,
                checksum=None if csums is None else csums[(idx - h) % n],
                live_nonzero=False)
        return (pl, acc + pl, ok), None

    (_, acc, ok), _ = lax.scan(hop, (payload, payload, jnp.bool_(True)),
                               jnp.arange(1, n))
    y = zebra_unpack_ref(acc, union, bs, bc)

    moved = ((n - 1) * stream_bytes(u_live, bs, bc, g2.dtype, nm * nk)
             ).astype(jnp.int32)
    dense = jnp.int32((n - 1) * M * K * item)
    if validation != "off":
        ok_ring = lax.psum(ok.astype(jnp.int32), axis) == n

        def retry_dense():
            jax.debug.callback(lambda t=tag: integrity.note_failure(t))
            return lax.psum(g2, axis)

        y = lax.cond(ok_ring, lambda: y, retry_dense)
        moved = jnp.where(ok_ring, moved, moved + dense)
    return y, union, LinkBytes(moved, dense)


def zebra_reduce_scatter(g2: jax.Array, axis, *, bs: int, bc: int,
                         bitmap: jax.Array | None = None,
                         validation: str = "off",
                         site: str = "reduce_scatter"
                         ) -> tuple[jax.Array, LinkBytes]:
    """Reduce-scatter over block rows: psum in payload form, each device
    keeps its ``M // n`` row chunk (must be bs-aligned, so chunks never
    straddle blocks). Accounted as a ring reduce-scatter — each inbound
    link carries the traveling partial of every chunk except the home
    chunk, at union capacity restricted to that chunk's block rows::

        moved = sum_{c != home} (union_live_c * bs * bc * itemsize
                                 + ceil(nb_c / 8))
        dense = (n - 1) * (M // n) * K * itemsize
    """
    M, K = g2.shape
    n = axis_size(axis)
    if n == 1:
        return g2, zero_link()
    if M % (n * bs):
        raise ValueError(
            f"zebra_reduce_scatter: M={M} must divide into {n} bs-aligned "
            f"chunks (bs={bs}) — resolve_comms should have degraded")
    Ml = M // n
    y, union, _ = zebra_psum_stream(g2, axis, bs=bs, bc=bc, bitmap=bitmap,
                                    validation=validation, site=site)
    idx = lax.axis_index(axis)
    out = lax.dynamic_slice_in_dim(y, idx * Ml, Ml, axis=0)

    nm_l, nk = Ml // bs, K // bc
    chunk_counts = union.reshape(n, nm_l, nk).astype(jnp.int32).sum((1, 2))
    chunk_streams = stream_bytes(chunk_counts, bs, bc, g2.dtype, nm_l * nk)
    moved = (jnp.sum(chunk_streams) - chunk_streams[idx]).astype(jnp.int32)
    item = jnp.dtype(g2.dtype).itemsize
    dense = jnp.int32((n - 1) * Ml * K * item)
    return out, LinkBytes(moved, dense)


# ---------------------------------------------------------------------------
# psum_exact_bytes — the shared exact-byte reduction (ffn / MoE / meter)
# ---------------------------------------------------------------------------

def psum_exact_bytes(nbytes, axes) -> tuple[jax.Array, jax.Array]:
    """Exact cross-shard sum of per-shard int32 byte counts, returned as
    the engine's f32 ``(hi, lo)`` base-2**24 pair (``LayerAux`` form).

    The psum runs on int32 legs split at base 2**16: each leg's sum
    stays far from int32 overflow up to ~32k shards, keeping the
    accounting exact end-to-end — an f32 psum would round as soon as
    the total crossed 16 MiB, an unsplit int32 psum overflows at ~128
    shards of 2 GiB maps. Recombination into the 2**24 pair happens in
    int32 (exact), then each leg casts to f32 (each < 2**24: exact).
    Extracted from the hand-rolled pair in ``models/lm/ffn.py`` so ffn,
    MoE and the per-link meter share ONE rule."""
    mb = jnp.asarray(nbytes).astype(jnp.int32)
    hi16 = lax.psum(mb // 65536, axes)
    lo16 = lax.psum(mb % 65536, axes)
    rem = (hi16 % 256) * 65536 + lo16
    hi = (hi16 // 256 + rem // MB_BASE).astype(jnp.float32)
    lo = (rem % MB_BASE).astype(jnp.float32)
    return hi, lo


# ---------------------------------------------------------------------------
# Capability resolution for layer exchanges
# ---------------------------------------------------------------------------

def resolve_comms(backend_name: str, *, rows: int, cols: int,
                  bs: int, bc: int) -> tuple[str | None, str | None]:
    """Decide how a layer exchange runs: ``("compressed", None)``,
    ``("dense", reason)``, or ``(None, None)`` when no comm context is
    active (no exchange at all — the single-process semantics every
    existing call site keeps).

    Mirrors the engine's ``_resolve_backend`` contract: the site's
    backend must declare the ``comms="compressed"`` capability
    (``core.backends``), the axis must actually be sharded, and the
    shard must tile into whole (bs, bc) blocks. Anything else degrades
    to a dense ``lax.all_gather`` with an explicit, logged reason."""
    info = comm_axis()
    if info is None:
        return None, None
    _, n = info
    from ..core.backends import backend_spec
    spec = backend_spec(backend_name)
    if spec.comms != "compressed":
        return "dense", "comms-capability"
    if n <= 1:
        return "dense", "single-device"
    if rows % bs or cols % bc:
        return "dense", "non-divisible"
    from ..ft.breaker import active_board
    board = active_board()
    if board is not None and not board.allow(RING_SITE):
        # per-boundary circuit breaker (ft.breaker): repeated classified
        # CorruptStream detections on the ring hop trip the whole
        # exchange to dense until a half-open probe passes
        return "dense", "breaker-open"
    return "compressed", None


def log_comm_degrade(site: str, backend: str, reason: str) -> None:
    key = (site, backend, reason)
    if key not in _DEGRADE_LOGGED:
        _DEGRADE_LOGGED.add(key)
        _log.info("compressed comms at %r: backend %r degraded to dense "
                  "all_gather (%s)", site, backend, reason)
