"""Unified Zebra site engine — ONE backend-dispatched execution path for
every activation site in the repo (CNN maps, LM FFN hidden maps, layer
outputs, KV caches), in BOTH training and inference.

The paper's pipeline is ``comparator -> block mask -> compressed DRAM
stream``; this module is the single code path that realizes it. Model code
never calls ``zebra_cnn`` / ``zebra_tokens`` / the Pallas kernels / the
stream codec directly — it calls :func:`zebra_site` and the engine picks
the execution backend from ``ZebraConfig.backend`` (with per-site
overrides via ``ZebraConfig.site_backends``):

``reference``
    Pure-jnp masking (``core.zebra``). The only backend that can serve
    threshold *nets* (per-sample learned thresholds + the Eq. 1
    regularizer); also the degrade target for every capability miss.
``pallas``
    The fused comparator kernel (``kernels.zebra_mask``): one VMEM pass
    computes block maxima, compares against T_obj and zeroes dead blocks.
    Bitwise-identical to reference — and *trainable*: in train mode the
    launch is wrapped in ``jax.custom_vjp`` (``kernels.grad``) whose
    backward implements the hard/STE/soft gradient modes.
``stream``
    ``zebra_mask_pack`` -> ``zebra_unpack``: the two-phase parallel
    producer (supertiled comparator pass + XLA exclusive scan +
    parallel pack pass) hands only the compressed ``(payload, bitmap)``
    stream to the expander — the dense masked map is never materialized
    by the producer, and no map is too big (the comparator pass tiles
    under ``tiles_for``; there is no whole-payload VMEM residency).
    ``SiteAux.measured_bytes`` reports the observed stream length
    (payload + packed index, the Eq. 2/3 observable). Numerically
    identical to reference — and trainable through the same custom_vjp,
    so the bytes observable stays live during training.
``fused``
    ``zebra_mask_pack`` -> ``zebra_spmm_cs``: the downstream matmul
    reads live blocks straight from the compressed payload via the
    bitmap's prefix-sum slot map in ``(stm, stk)`` supertile steps
    (``tiles_for(kind="gemm")``) and *skips* dead K-blocks in
    whole-supertile chunks without ever unpacking (dynamic feature-map
    pruning, Liang et al. 2018 style). Needs the downstream weight
    ``w``; used by the dense FFN ``w_down``. Byte accounting is the
    same ``stream_bytes`` helper as stream. Infer-only (the
    payload-consuming GEMM has no backward rule) — train-mode requests
    degrade to reference.

Capability resolution. Which backend actually executes is decided by the
:mod:`core.backends` registry: each :class:`~repro.core.backends.
BackendSpec` declares ``trainable`` / ``emits_stream`` / ``consumes_w``
/ ``vmem_bounded``, and :func:`zebra_site` resolves the site's
(mode, threshold-net, shape) situation against those capabilities. A
request the backend cannot serve degrades to ``reference`` with an
explicit reason — logged once per (site, backend, reason) and surfaced
in ``SiteAux.backend`` as ``"reference(<reason>)"``; there are no
implicit rewrites. The current reasons:

``tnet``             train mode with a threshold net: per-sample learned
                     thresholds (and their Eq. 1 gradient) are jnp-only.
``not-trainable``    train mode on a backend without a custom_vjp
                     backward (``fused``).
``degenerate-rows``  token maps whose S doesn't divide ``block_seq``
                     (e.g. single-token decode) degrade to ``bs=1`` — a
                     one-row "block" has no skippable HBM tile, so
                     kernel dispatch would be pure overhead.
``vmem-bounded``     a backend declaring ``vmem_bounded`` asked to run a
                     map bigger than ``vmem_budget_bytes``. The built-in
                     compressed backends self-tile (declare False); the
                     reason exists for registered backends that cannot.
``narrow-blocks``    on a TPU, blocks narrower than a (8, 128) vreg tile
                     (the paper's 4x4 NCHW blocks; ``kernels.platform.
                     tpu_tileable``). No Pallas form addresses them, and
                     the XLA forms of the stream (an ``(n, 4, 4)`` payload
                     gather and its expansion) pad each block 64x in HBM
                     and compile for minutes per site shape. The site
                     runs the reference masked map and moves no stream
                     (``measured_bytes`` 0). Off a TPU such blocks run the
                     selected backend: interpreted kernels, XLA pack and
                     expand forms.

Layouts. ``tokens`` maps ``(..., S, D)`` tile into ``(block_seq,
block_ch)`` VMEM blocks. ``nchw`` maps ``(B, C, H, W)`` use the paper's
spatial ``b x b`` blocks per channel; the engine flattens them onto the
kernels' 2-D ``(M, K)`` tile grid as ``(B*C*H, W)`` with ``bs = bc = b``
— every ``(b, b)`` tile of that matrix is exactly one spatial block of
one channel (H, W divide by b, so tiles never straddle planes). NCHW
blocks shrink to the largest divisor of (H, W) (paper: "block size 2
when the map goes to 2x2") and stay on the selected backend, except on
a TPU (``narrow-blocks``).

New backends register through :func:`register_engine_backend` — model
code needs no changes, which is the structural point of the registry.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from . import backends
from .backends import BackendSpec, backend_names, backend_spec
from .zebra import (ZebraConfig, effective_tnet, require_tnet, zebra_cnn,
                    zebra_tokens)

_log = logging.getLogger("repro.engine")
_DEGRADE_LOGGED: set[tuple[str, str, str]] = set()


# ---------------------------------------------------------------------------
# The uniform per-site aux struct
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SiteAux:
    """What one Zebra site reports, uniformly across backends.

    ``reg``             Eq. 1 regularizer term: threshold-net L2 pull in
                        tnet-train mode; the realized zero-block count
                        (zero_frac · n_blocks, stop-gradiented) in
                        constant-threshold train mode; 0 in infer mode.
    ``zero_frac``       fraction of blocks masked to zero at this site.
    ``measured_bytes``  observed transport bytes (payload + packed index)
                        for the whole input, exact int32; 0 for backends
                        that move the map dense (reference/pallas) or do
                        not run.
    ``n_blocks``        static per-sample block count (0 when disabled),
                        the weight used by ``mean_zero_frac``.
    ``thresholds``      train-mode threshold-net outputs (None otherwise).
    ``backend``         which backend actually executed (static). A
                        capability degrade is surfaced here as
                        ``"reference(<reason>)"``; a degraded layer
                        exchange appends ``"+dense-comms(<reason>)"``.
    ``ici_bytes``       interconnect bytes this site's layer exchanges
                        put on ONE inbound link (compressed stream on
                        the compressed path, dense size on a degraded
                        exchange); 0 outside a comm context. Attached by
                        ``distributed.collectives.attach_link``.
    ``ici_dense_bytes`` dense-equivalent per-link bytes of the same
                        exchanges (the ``lax.all_gather`` baseline the
                        compression is measured against).

    Supports dict-style access (``aux["zero_frac"]``, ``aux.get(...)``)
    so it is a drop-in for the legacy per-site aux dicts.
    """
    reg: Any = 0.0
    zero_frac: Any = 0.0
    measured_bytes: Any = 0.0
    n_blocks: Any = 0
    thresholds: Any = None
    backend: str = "reference"
    ici_bytes: Any = 0
    ici_dense_bytes: Any = 0

    def tree_flatten(self):
        return ((self.reg, self.zero_frac, self.measured_bytes,
                 self.n_blocks, self.thresholds, self.ici_bytes,
                 self.ici_dense_bytes), (self.backend,))

    @classmethod
    def tree_unflatten(cls, aux, children):
        reg, zf, mb, nb, thr, ici, icid = children
        return cls(reg=reg, zero_frac=zf, measured_bytes=mb, n_blocks=nb,
                   thresholds=thr, backend=aux[0], ici_bytes=ici,
                   ici_dense_bytes=icid)

    # legacy dict-style access (pre-engine aux shape)
    def __getitem__(self, key: str):
        return getattr(self, key)

    def get(self, key: str, default=None):
        return getattr(self, key, default)

    @classmethod
    def empty(cls, backend: str = "disabled") -> "SiteAux":
        return cls(reg=jnp.float32(0.0), zero_frac=jnp.float32(0.0),
                   measured_bytes=jnp.int32(0), n_blocks=0,
                   thresholds=None, backend=backend,
                   ici_bytes=jnp.int32(0), ici_dense_bytes=jnp.int32(0))


MB_BASE = 16777216             # 2**24 — f32 integers are exact below this
_MB_BASE = float(MB_BASE)


def add_byte_pair(hi_a, lo_a, hi_b, lo_b):
    """Add two (hi, lo) base-2**24 byte pairs exactly.

    The lo legs are added in int32: each is an exact integer < 2**24, but
    their f32 SUM can land between representable values above 2**24 (odd
    sums round) — the carry must be extracted from an exact sum. The ONE
    carry rule; LayerAux.__add__ and the train-step microbatch
    accumulator both use it. Inputs coerce through jnp.asarray so a
    defaulted Python-float leg (e.g. LayerAux ici fields a constructor
    left at 0.0) adds exactly like a jnp scalar."""
    lo = jnp.asarray(lo_a).astype(jnp.int32) + jnp.asarray(lo_b).astype(jnp.int32)
    hi = (jnp.asarray(hi_a, jnp.float32) + jnp.asarray(hi_b, jnp.float32)
          + (lo // jnp.int32(MB_BASE)).astype(jnp.float32))
    return hi, (lo % jnp.int32(MB_BASE)).astype(jnp.float32)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LayerAux:
    """Site aux accumulated across layers/sites — the scan-carry form.

    f32 scalars so it rides ``jax.lax.scan`` carries and jit boundaries.
    ``zf_blocks`` is Σ zero_frac·n_blocks, so ``zero_frac`` (the
    property) is the block-count-weighted mean with a guard for the
    no-divisible-leaf / no-site case (n_blocks == 0 -> 0, no div/0).

    Measured bytes ride the carry as the exact f32 pair ``(mb_hi,
    mb_lo)`` with base 2**24: per-site counts are int32-exact, but a
    single f32 accumulator would start rounding as soon as the running
    total crossed 16 MiB. The pair keeps accumulation exact to 2**48
    bytes; read it back with :meth:`measured_bytes_exact` (host) — the
    in-graph ``measured_bytes`` property is a display convenience that
    rounds above 16 MiB.

    Interconnect bytes (``SiteAux.ici_bytes`` / ``ici_dense_bytes``,
    attached by the compressed collectives) accumulate through the same
    pair scheme — ``(ici_hi, ici_lo)`` for what layer exchanges actually
    put on one inbound link, ``(ici_dense_hi, ici_dense_lo)`` for the
    dense-equivalent baseline. They total across ALL exchanges a layer
    ran; per-axis breakdown lives in ``compress.meter.BandwidthMeter``
    link records (the axis is host-side metadata, not a carry). The
    fields default to 0.0 so pre-existing constructors stay valid —
    ``add_byte_pair`` coerces, and ``zero()``/``of_site`` produce jnp
    scalars so scan carries keep a consistent pytree.
    """
    reg: jax.Array
    zf_blocks: jax.Array
    n_blocks: jax.Array
    mb_hi: jax.Array
    mb_lo: jax.Array
    router_aux: jax.Array
    ici_hi: Any = 0.0
    ici_lo: Any = 0.0
    ici_dense_hi: Any = 0.0
    ici_dense_lo: Any = 0.0

    def tree_flatten(self):
        return ((self.reg, self.zf_blocks, self.n_blocks,
                 self.mb_hi, self.mb_lo, self.router_aux,
                 self.ici_hi, self.ici_lo,
                 self.ici_dense_hi, self.ici_dense_lo), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def zero(cls) -> "LayerAux":
        z = jnp.float32(0.0)
        return cls(z, z, z, z, z, z, z, z, z, z)

    @classmethod
    def of_site(cls, site: SiteAux, router_aux=0.0) -> "LayerAux":
        nb = jnp.float32(site.n_blocks)
        base = jnp.int32(_MB_BASE)

        def pair(v):
            v = jnp.asarray(v).astype(jnp.int32)
            return ((v // base).astype(jnp.float32),
                    (v % base).astype(jnp.float32))

        mb_hi, mb_lo = pair(site.measured_bytes)
        ici_hi, ici_lo = pair(site.ici_bytes)
        icid_hi, icid_lo = pair(site.ici_dense_bytes)
        return cls(reg=jnp.float32(site.reg),
                   zf_blocks=jnp.float32(site.zero_frac) * nb,
                   n_blocks=nb,
                   mb_hi=mb_hi, mb_lo=mb_lo,
                   router_aux=jnp.float32(router_aux),
                   ici_hi=ici_hi, ici_lo=ici_lo,
                   ici_dense_hi=icid_hi, ici_dense_lo=icid_lo)

    def __add__(self, other: "LayerAux") -> "LayerAux":
        hi, lo = add_byte_pair(self.mb_hi, self.mb_lo,
                               other.mb_hi, other.mb_lo)
        ihi, ilo = add_byte_pair(self.ici_hi, self.ici_lo,
                                 other.ici_hi, other.ici_lo)
        dhi, dlo = add_byte_pair(self.ici_dense_hi, self.ici_dense_lo,
                                 other.ici_dense_hi, other.ici_dense_lo)
        return LayerAux(self.reg + other.reg,
                        self.zf_blocks + other.zf_blocks,
                        self.n_blocks + other.n_blocks,
                        hi, lo,
                        self.router_aux + other.router_aux,
                        ihi, ilo, dhi, dlo)

    @property
    def zero_frac(self) -> jax.Array:
        return jnp.clip(self.zf_blocks / jnp.maximum(self.n_blocks, 1.0),
                        0.0, 1.0)

    @property
    def measured_bytes(self) -> jax.Array:
        """In-graph f32 readout (rounds above 16 MiB — display only)."""
        return self.mb_hi * jnp.float32(_MB_BASE) + self.mb_lo

    def measured_bytes_exact(self) -> int:
        """Exact host-side readout of the accumulated byte pair."""
        return int(float(self.mb_hi)) * int(_MB_BASE) + int(float(self.mb_lo))

    @property
    def ici_bytes(self) -> jax.Array:
        """In-graph f32 readout of per-link interconnect bytes (display)."""
        return (jnp.asarray(self.ici_hi, jnp.float32) * jnp.float32(_MB_BASE)
                + jnp.asarray(self.ici_lo, jnp.float32))

    @property
    def ici_dense_bytes(self) -> jax.Array:
        return (jnp.asarray(self.ici_dense_hi, jnp.float32)
                * jnp.float32(_MB_BASE)
                + jnp.asarray(self.ici_dense_lo, jnp.float32))

    def ici_bytes_exact(self) -> tuple[int, int]:
        """Exact host-side (moved, dense-equivalent) per-link totals."""
        moved = (int(float(self.ici_hi)) * int(_MB_BASE)
                 + int(float(self.ici_lo)))
        dense = (int(float(self.ici_dense_hi)) * int(_MB_BASE)
                 + int(float(self.ici_dense_lo)))
        return moved, dense


# ---------------------------------------------------------------------------
# Block-layout helpers
# ---------------------------------------------------------------------------

def site_block(h: int, w: int, want: int) -> int:
    """Largest block size <= want dividing both map sides (paper §II.A:
    shrink when the map is smaller than the block, e.g. 2 for 2x2 maps)."""
    b = min(want, h, w)
    while h % b or w % b:
        b -= 1
    return max(b, 1)


def nchw_stream_dims(shape: tuple[int, ...], block_hw: int
                     ) -> tuple[int, int, int] | None:
    """(B, C, H, W) -> (M, K, b): the 2-D tile-grid view whose (b, b)
    tiles are exactly the paper's spatial blocks. None if not 4-D."""
    if len(shape) != 4:
        return None
    B, C, H, W = shape
    b = site_block(H, W, block_hw)
    return B * C * H, W, b


def _tokens_blocks(x: jax.Array, cfg: ZebraConfig) -> tuple[int, int, bool]:
    """Effective (bs, bc) for a (..., S, D) map + whether bs degenerated."""
    S, D = x.shape[-2], x.shape[-1]
    bs = cfg.block_seq if S % cfg.block_seq == 0 else 1
    bc = cfg.block_ch if D % cfg.block_ch == 0 else D
    return bs, bc, (bs == 1 and cfg.block_seq > 1)


def _index_bytes(n_blocks_total: int) -> int:
    return (n_blocks_total + 7) // 8


def stream_bytes(n_live: jax.Array, bs: int, bc: int, dtype,
                 n_blocks_total: int) -> jax.Array:
    """Observed stream length (Eq. 2/3): live payload + packed index.

    The ONE byte-accounting rule shared by every compressed backend —
    ``n_live`` is the producer kernel's counter output, so stream and
    fused cannot drift apart in how they reconcile against Eq. 2/3.
    Integer arithmetic: exact (the sub-1-byte reconciliation bound must
    hold per site) for payloads up to 2 GiB; float32 would already round
    above 16 MiB. Cross-site accumulation stays exact via the
    ``LayerAux`` (mb_hi, mb_lo) pair.
    """
    item = jnp.dtype(dtype).itemsize
    return (n_live.astype(jnp.int32) * (bs * bc * item)
            + _index_bytes(n_blocks_total))


def merge_site_aux(a: SiteAux, b: SiteAux) -> SiteAux:
    """Fold two sites' aux into ONE SiteAux: block-weighted zero_frac,
    summed reg/measured/ici legs, joined backend label. For call sites
    whose public contract is a single aux but that execute an auxiliary
    site — e.g. ``ffn_apply`` masking its layer output for the
    compressed TP exchange under a comm context. Thresholds keep ``a``'s
    (the primary site's) outputs — the auxiliary site never runs a
    threshold net."""
    na, nb = int(a.n_blocks), int(b.n_blocks)
    nt = max(na + nb, 1)
    zf = (jnp.float32(a.zero_frac) * na + jnp.float32(b.zero_frac) * nb) / nt
    as_i32 = lambda v: jnp.asarray(v).astype(jnp.int32)
    return SiteAux(
        reg=a.reg + b.reg, zero_frac=zf,
        measured_bytes=as_i32(a.measured_bytes) + as_i32(b.measured_bytes),
        n_blocks=na + nb, thresholds=a.thresholds,
        backend=f"{a.backend}+{b.backend}",
        ici_bytes=as_i32(a.ici_bytes) + as_i32(b.ici_bytes),
        ici_dense_bytes=(as_i32(a.ici_dense_bytes)
                         + as_i32(b.ici_dense_bytes)))


# ---------------------------------------------------------------------------
# Backend implementations — each maps (x2 (M, K), bs, bc, cfg) -> (y2, aux)
# ---------------------------------------------------------------------------

def _kernel_statics(variant: str, x2: jax.Array, bs: int, bc: int,
                    cfg: ZebraConfig):
    """Static launch config for ``kernels.grad.launch_forward`` — the ONE
    forward pipeline shared by infer dispatch and the custom_vjp train
    path, so the two cannot drift apart. The two-phase producer tiles
    its comparator pass with the same ``tiles_for`` supertile as the
    mask variant, so no map is ever over budget (the old
    whole-payload-resident producer needed a fits-VMEM degrade here)."""
    from ..kernels import supertile as st
    from ..kernels.grad import KernelStatics
    M, K = x2.shape
    item = jnp.dtype(x2.dtype).itemsize
    tm, tk = cfg.tiles_for(M, K, bs, bc, x2.dtype)
    gtm, gtk = cfg.tiles_for(M, K, bs, bc, x2.dtype, kind="gather")
    pw = st.pack_window((M // bs) * (K // bc), bs, bc, item,
                        int(cfg.vmem_budget_bytes))
    return KernelStatics(variant=variant, t_obj=cfg.t_obj, bs=bs, bc=bc,
                         tm=tm, tk=tk, gtm=gtm, gtk=gtk, pw=pw,
                         grad_mode=cfg.grad_mode,
                         soft_temp=cfg.soft_temp)


def _run_pallas(x2: jax.Array, bs: int, bc: int, cfg: ZebraConfig):
    from ..kernels.grad import launch_forward
    y2, bitmap, _ = launch_forward(x2, _kernel_statics("mask", x2, bs, bc, cfg))
    return y2, bitmap, jnp.int32(0)


def _mask_pack(x2: jax.Array, bs: int, bc: int, cfg: ZebraConfig):
    """Two-phase parallel producer: compressed stream out, the dense
    masked map never materialized; comparator pass tiled by tiles_for,
    pack pass windowed under the same budget."""
    from ..kernels import supertile as st
    from ..kernels.mask_pack import zebra_mask_pack
    M, K = x2.shape
    tm, tk = cfg.tiles_for(M, K, bs, bc, x2.dtype)
    window = st.pack_window((M // bs) * (K // bc), bs, bc,
                            jnp.dtype(x2.dtype).itemsize,
                            int(cfg.vmem_budget_bytes))
    return zebra_mask_pack(x2, t_obj=cfg.t_obj, bs=bs, bc=bc, tm=tm, tk=tk,
                           window=window)


def _run_stream(x2: jax.Array, bs: int, bc: int, cfg: ZebraConfig):
    """mask_pack -> unpack with only the (payload, bitmap) stream between
    producer and expander. Any map size fits: the producer's comparator
    pass tiles under cfg.tiles_for, the pack pass touches one payload
    slot window per step (no whole-payload VMEM residency)."""
    from ..kernels.grad import launch_forward
    y2, bitmap, n_live = launch_forward(
        x2, _kernel_statics("stream", x2, bs, bc, cfg))
    return y2, bitmap, stream_bytes(n_live, bs, bc, x2.dtype, bitmap.size)


def _run_fused(x2: jax.Array, w: jax.Array, bs: int, bc: int,
               cfg: ZebraConfig) -> tuple[jax.Array, jax.Array, jax.Array]:
    """mask_pack -> payload-consuming GEMM: the consumer reads each K
    column's live blocks as one contiguous run of the consumer-ordered
    payload through the static prefetch schedule (kernels.schedule) —
    dead blocks are skipped, the dense map is never unpacked. The full
    cached plan (cfg.gemm_plan_for: kernel-form supertile + the
    scheduled capacity ladder, tightened by cfg.zero_frac_hint) is
    threaded through, so repeated site launches hit the plan cache.
    Returns (x' @ w, bitmap, fetched bytes)."""
    from ..kernels.spmm_cs import zebra_spmm_cs
    M, K = x2.shape
    payload, bitmap, n_live = _mask_pack(x2, bs, bc, cfg)
    plan = cfg.gemm_plan_for(M, K, bs, bc, x2.dtype, n=w.shape[-1])
    out = zebra_spmm_cs(payload, w, bitmap, bs=bs, bc=bc, bn=plan.bn,
                        stm=plan.stm, stk=plan.stk, caps=plan.caps,
                        zero_frac_hint=cfg.zero_frac_hint)
    measured = stream_bytes(n_live, bs, bc, x2.dtype, bitmap.size)
    return out.astype(x2.dtype), bitmap, measured


# ---------------------------------------------------------------------------
# Infer-path dispatch table — (x2, bs, bc, cfg, w) -> (y2, bitmap,
# measured_bytes, n_cols|None). n_cols None = map-shaped output.
# ---------------------------------------------------------------------------

def _impl_pallas(x2, bs, bc, cfg, w=None):
    y2, bitmap, measured = _run_pallas(x2, bs, bc, cfg)
    return y2, bitmap, measured, None


def _impl_stream(x2, bs, bc, cfg, w=None):
    y2, bitmap, measured = _run_stream(x2, bs, bc, cfg)
    return y2, bitmap, measured, None


def _impl_fused(x2, bs, bc, cfg, w=None):
    if w is None:                       # no downstream weight: mask-only
        return _impl_pallas(x2, bs, bc, cfg)
    out, bitmap, measured = _run_fused(x2, w, bs, bc, cfg)
    return out, bitmap, measured, w.shape[-1]


_INFER_IMPLS: dict[str, Callable] = {
    "pallas": _impl_pallas,
    "stream": _impl_stream,
    "fused": _impl_fused,
}


# ---------------------------------------------------------------------------
# Validated ingest (cfg.validation != "off") — the wire contract enforced
# at the producer -> consumer boundary, with recompute-from-dense recovery
# ---------------------------------------------------------------------------

def _validated_stream_impl(x2: jax.Array, bs: int, bc: int, cfg: ZebraConfig,
                           w: jax.Array | None = None, *, site: str = ""):
    """The stream/fused pipeline with the ``compress.integrity`` contract
    checked between producer and consumer: mask_pack -> (chaos tap) ->
    ``check_stream`` -> unpack / payload GEMM, with a ``lax.cond``
    recovery branch that recomputes from the dense map still in hand
    (``ft.faults`` policy "recompute-dense" — the dense source of an
    engine-internal stream is x2 itself). The recovery branch fires
    ``integrity.note_failure`` via ``jax.debug.callback`` so detections
    are observable from outside the jit. Checksum level seals the stream
    BEFORE the tap — corruption in flight must break the fold."""
    from ..compress import integrity
    from ..ft.inject import stream_tap
    from ..kernels.ref import zebra_mask_ref, zebra_unpack_ref

    level = cfg.validation
    tag = f"engine:{site or 'map'}"
    M, K = x2.shape
    payload, bitmap, n_live = _mask_pack(x2, bs, bc, cfg)
    csum = (integrity.stream_checksum(payload, bitmap, n_live)
            if level == "checksum" else None)
    payload, bitmap, n_live = stream_tap(payload, bitmap, n_live, site=tag)
    ok = integrity.check_stream(payload, bitmap, n_live, level=level,
                                checksum=csum,
                                live_nonzero=cfg.t_obj > 0)

    def recover_mask():
        jax.debug.callback(lambda t=tag: integrity.note_failure(t))
        return zebra_mask_ref(x2, cfg.t_obj, bs, bc)

    if w is None:
        y2, bm = lax.cond(
            ok,
            lambda: (zebra_unpack_ref(payload, bitmap, bs, bc),
                     bitmap.astype(jnp.int8)),
            recover_mask)
        n_cols = None
    else:
        from ..kernels.spmm_cs import zebra_spmm_cs
        plan = cfg.gemm_plan_for(M, K, bs, bc, x2.dtype, n=w.shape[-1])

        def consume():
            out = zebra_spmm_cs(payload, w, bitmap, bs=bs, bc=bc, bn=plan.bn,
                                stm=plan.stm, stk=plan.stk, caps=plan.caps,
                                zero_frac_hint=cfg.zero_frac_hint)
            return out.astype(x2.dtype), bitmap.astype(jnp.int8)

        def recover():
            y, keep = recover_mask()
            return ((y.astype(jnp.float32) @ w.astype(jnp.float32))
                    .astype(x2.dtype), keep)

        y2, bm = lax.cond(ok, consume, recover)
        n_cols = w.shape[-1]
    n_keep = jnp.sum(bm.astype(jnp.int32))
    measured = stream_bytes(n_keep, bs, bc, x2.dtype, bm.size)
    return y2, bm, measured, n_cols


_VALIDATED_BACKENDS = ("stream", "fused")


def register_engine_backend(spec: BackendSpec, infer_impl: Callable,
                            forward_variant: Callable | None = None
                            ) -> BackendSpec:
    """Register a new execution backend end-to-end: declare its
    capabilities in the :mod:`core.backends` registry and provide the
    infer-path impl ``(x2, bs, bc, cfg, w) -> (y2, bitmap,
    measured_bytes, n_cols|None)``. A ``trainable`` spec must also bring
    its forward pipeline ``(x2, statics) -> (y2, bitmap, n_live)`` —
    registered under ``spec.grad_variant`` so train mode dispatches the
    same launches through the shared custom_vjp (``kernels.grad``) —
    unless it reuses a built-in variant. Model code needs no changes —
    every site already dispatches through :func:`zebra_site` by name."""
    from ..kernels import grad
    if forward_variant is not None:
        grad.register_forward_variant(spec.grad_variant, forward_variant)
    elif spec.trainable and spec.name != "reference" \
            and not grad.has_forward_variant(spec.grad_variant):
        raise ValueError(
            f"backend {spec.name!r} declares trainable=True with unknown "
            f"grad_variant {spec.grad_variant!r}; pass forward_variant= or "
            f"reuse a built-in variant")
    backends.register_backend(spec)
    _INFER_IMPLS[spec.name] = infer_impl
    return spec


# ---------------------------------------------------------------------------
# Capability resolution
# ---------------------------------------------------------------------------

def _resolve_backend(spec: BackendSpec, *, mode: str, tnet,
                     degenerate: bool, over_budget: bool = False,
                     narrow: bool = False) -> tuple[str, str | None]:
    """Map one site's situation onto a backend the spec can serve.

    Returns ``(final backend name, degrade reason | None)`` — the single
    place train/infer/shape legality is decided (no implicit rules at
    call sites). ``over_budget`` only matters for backends declaring
    ``vmem_bounded``: their whole-map working set must fit
    ``vmem_budget_bytes`` (the built-in compressed backends self-tile
    and declare False, so they never degrade here). ``narrow`` is set
    on a TPU for blocks no Pallas form can tile."""
    if spec.name == "reference":
        return "reference", None
    if mode == "train" and not spec.trainable:
        return "reference", "not-trainable"
    if mode == "train" and tnet is not None:
        return "reference", "tnet"      # learned per-sample thresholds + the
                                        # Eq. 1 threshold gradient are jnp-only
    if degenerate:
        return "reference", "degenerate-rows"
    if spec.vmem_bounded and over_budget:
        return "reference", "vmem-bounded"
    if narrow:
        return "reference", "narrow-blocks"
    return spec.name, None


def _log_resolution(site: str, requested: str, label: str,
                    degraded: bool) -> None:
    """A degrade is logged once per (site, backend, label) at INFO."""
    key = (site, requested, label)
    if degraded and key not in _DEGRADE_LOGGED:
        _DEGRADE_LOGGED.add(key)
        _log.info("zebra_site %r: backend %r resolved as %s",
                  site, requested, label)


def wants_fused(cfg: ZebraConfig, site: str = "") -> bool:
    """True when this site should hand its downstream weight to the
    engine: the configured backend consumes ``w`` AND the capability
    resolution keeps it (a train-mode request on a non-trainable
    w-consumer degrades, so the caller keeps its dense matmul and remat
    annotations)."""
    if not cfg.enabled:
        return False
    spec = backend_spec(cfg.backend_for(site))
    if not spec.consumes_w or spec.name == "reference":
        return False
    final, _ = _resolve_backend(spec, mode=cfg.mode, tnet=None,
                                degenerate=False)
    return final == spec.name


# ---------------------------------------------------------------------------
# The engine entry point
# ---------------------------------------------------------------------------

def zebra_site(x: jax.Array, cfg: ZebraConfig, *, site: str = "",
               layout: str = "tokens", tnet: dict | None = None,
               w: jax.Array | None = None) -> tuple[jax.Array, SiteAux]:
    """Execute one Zebra activation site through the configured backend.

    x       ``tokens``: (..., S, D) activation map (leading dims = batch);
            ``nchw``: (B, C, H, W) CNN map.
    site    name used for per-site backend overrides (cfg.site_backends).
    tnet    threshold-net params (tnet-train sites resolve to reference).
    w       downstream weight (K, N) — only for backends whose spec
            declares ``consumes_w``; the site then returns ``mask(x) @ w``
            instead of the masked map.

    Works in train and infer mode on every backend: train-mode kernel
    dispatch goes through ``kernels.grad.zebra_kernel_trainable``
    (custom_vjp), so ``jax.grad`` through a pallas/stream site equals the
    reference path. Capability misses degrade to reference with the
    reason in ``SiteAux.backend`` (see module docstring).

    Everything the site runs, on every backend and layout, sits under the
    named scope ``zebra.<site>`` (``zebra`` for an unnamed site), so the
    compiled program's ops, fused or not, carry the site in their
    ``op_name`` and a device trace can attribute them.

    Returns ``(y, SiteAux)``. Without ``w``, y is the masked map (bitwise
    identical across reference/pallas/stream). With ``w`` (fused), y is
    the downstream product with dead blocks skipped.
    """
    with jax.named_scope(f"zebra.{site}" if site else "zebra"):
        return _site(x, cfg, site=site, layout=layout, tnet=tnet, w=w)


def _site(x: jax.Array, cfg: ZebraConfig, *, site: str, layout: str,
          tnet: dict | None, w: jax.Array | None
          ) -> tuple[jax.Array, SiteAux]:
    spec = backend_spec(cfg.backend_for(site))
    if w is not None and not spec.consumes_w:
        raise ValueError(
            f"backend {spec.name!r} does not consume a downstream weight "
            f"(site={site!r}); apply the matmul at the call site instead")
    if not cfg.enabled:
        return (x if w is None else x @ w), SiteAux.empty()
    tnet = effective_tnet(cfg, tnet)
    require_tnet(cfg, tnet, site)

    # ---- layout -> 2-D tile grid + effective blocks -----------------------
    if layout == "nchw":
        B, C, H, W = x.shape
        b = site_block(H, W, cfg.block_hw)
        cfg = cfg.replace(block_hw=b)
        bs = bc = b
        dims = (B * C * H, W)
        nb_sample = C * (H // b) * (W // b)
        degenerate = False
    elif layout == "tokens":
        if x.ndim == 2:                 # bare (M, K) map: one-sample batch
            y, aux = _site(x[None], cfg, site=site, layout=layout,
                           tnet=tnet, w=w)
            return y[0], aux
        bs, bc, degenerate = _tokens_blocks(x, cfg)
        cfg = cfg.replace(block_seq=bs, block_ch=bc)
        S, D = x.shape[-2], x.shape[-1]
        dims = (x.size // D, D)
        nb_sample = (S // bs) * (D // bc)
    else:
        raise ValueError(f"unknown layout {layout!r}")

    over_budget = (spec.vmem_bounded and
                   dims[0] * dims[1] * jnp.dtype(x.dtype).itemsize
                   > cfg.vmem_budget_bytes)
    from ..kernels.platform import pallas_interpret, tpu_tileable
    backend, reason = _resolve_backend(
        spec, mode=cfg.mode, tnet=tnet, degenerate=degenerate,
        over_budget=over_budget,
        narrow=not (pallas_interpret() or tpu_tileable(bs, bc)))
    label = backend if reason is None else f"{backend}({reason})"
    _log_resolution(site, spec.name, label, reason is not None)

    # ---- reference: the jnp path (threshold nets live here) ---------------
    if backend == "reference":
        fn = zebra_cnn if layout == "nchw" else zebra_tokens
        y, aux = fn(x, cfg, tnet)
        if w is not None:               # w-consuming request degraded here
            y = y @ w
        return y, SiteAux(reg=aux["reg"], zero_frac=aux["zero_frac"],
                          measured_bytes=jnp.int32(0),
                          n_blocks=aux["n_blocks"],
                          thresholds=aux["thresholds"], backend=label)

    # ---- kernel backends on the flattened (M, K) grid ---------------------
    x2 = x.reshape(dims)
    if cfg.mode == "train":
        # trainable kernel path: custom_vjp forward = the same kernel
        # pipeline infer dispatches, backward = the configured gradient
        # mode (kernels.grad)
        from ..kernels.grad import zebra_kernel_trainable
        statics = _kernel_statics(spec.grad_variant, x2, bs, bc, cfg)
        y2, _, _ = zebra_kernel_trainable(x2, statics)
        # Observables are recomputed from the stop-gradient'd masked map,
        # NOT from the launch's bitmap/n_live outputs: integer custom_vjp
        # outputs materialize float0 tangents under jax.checkpoint'd layer
        # bodies (remat) that downstream arithmetic cannot consume. Live
        # blocks keep their values bitwise, so blockmax(|y|) >= t_obj IS
        # the kernel's keep bitmap (dead blocks are exact zeros).
        from ..kernels.zebra_mask import block_keep
        keep = block_keep(jax.lax.stop_gradient(y2), cfg.t_obj, bs, bc) != 0
        measured = (stream_bytes(jnp.sum(keep.astype(jnp.int32)), bs, bc,
                                 x2.dtype, keep.size)
                    if spec.emits_stream else jnp.int32(0))
        y = y2.reshape(x.shape)
        zero_frac = 1.0 - jnp.mean(keep.astype(jnp.float32))
        # realized Eq. 1 observable under the deployed constant thresholds
        reg = zero_frac * nb_sample
        return y, SiteAux(reg=reg, zero_frac=zero_frac,
                          measured_bytes=measured, n_blocks=nb_sample,
                          thresholds=None, backend=label)

    if cfg.validation != "off" and backend in _VALIDATED_BACKENDS:
        y2, bitmap, measured, n_cols = _validated_stream_impl(
            x2, bs, bc, cfg, w if backend == "fused" else None, site=site)
    else:
        y2, bitmap, measured, n_cols = _INFER_IMPLS[backend](x2, bs, bc, cfg, w)
    y = (y2.reshape(x.shape) if n_cols is None
         else y2.reshape(*x.shape[:-1], n_cols))
    zero_frac = 1.0 - jnp.mean(bitmap.astype(jnp.float32))
    return y, SiteAux(reg=jnp.float32(0.0), zero_frac=zero_frac,
                      measured_bytes=measured, n_blocks=nb_sample,
                      thresholds=None, backend=label)
