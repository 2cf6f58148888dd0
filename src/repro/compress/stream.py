"""Compressed activation stream — the transport form of a Zebra-masked map.

This is the byte-level object the paper's accelerator moves over DRAM
(Eq. 2/3): a dense payload of the surviving ``(bs, bc)`` blocks plus a
packed 1-bit-per-block keep index. See README.md §Compressed activation
transport for the exact layout.

``CompressedMap`` is a pytree, so it can cross jit boundaries, be shipped
between hosts, or sit in a checkpoint. Measured byte counts
(``payload_bytes`` / ``index_bytes``) are *observed* stream lengths, which
``BandwidthMeter`` reconciles against the analytic ``stored_bits``
prediction from ``core.bandwidth``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core.bandwidth import TokenMapSpec
from ..kernels import ref
from ..kernels.mask_pack import zebra_mask_pack
from ..kernels.pack import zebra_pack, zebra_unpack
from ..utils import cdiv


# ---------------------------------------------------------------------------
# 1-bit block index (Eq. 3): little-endian bit order, row-major block order
# ---------------------------------------------------------------------------

def pack_bitmap(bitmap: jax.Array) -> jax.Array:
    """(Mb, Kb) keep flags -> (ceil(n_blocks/8),) uint8. Bit b of byte i is
    block i*8 + b (little-endian within the byte)."""
    flat = bitmap.reshape(-1).astype(jnp.uint8)
    n = flat.shape[0]
    pad = cdiv(n, 8) * 8 - n
    flat = jnp.pad(flat, (0, pad))
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    return jnp.sum(flat.reshape(-1, 8) * weights, axis=1).astype(jnp.uint8)


def unpack_bitmap(packed: jax.Array, nm: int, nk: int) -> jax.Array:
    """Inverse of pack_bitmap -> (nm, nk) int8 keep flags."""
    bits = (packed[:, None] >> jnp.arange(8, dtype=jnp.uint8)) & jnp.uint8(1)
    return bits.reshape(-1)[: nm * nk].reshape(nm, nk).astype(jnp.int8)


# ---------------------------------------------------------------------------
# The stream object
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CompressedMap:
    """One compressed activation map: worst-case payload buffer (live blocks
    first, zero tail), packed index, and the measured live count.

    ``checksum`` is the optional in-band integrity word
    (``compress.integrity.stream_checksum`` — uint32 position-mixed XOR
    fold over bitmap bits + live payload + n_live). ``None`` (default)
    keeps the pre-integrity wire format; producers attach it when
    ``ZebraConfig.validation == "checksum"`` and ingest boundaries
    recompute and compare."""
    payload: jax.Array          # (n_blocks, bs, bc), activation dtype
    index: jax.Array            # (ceil(n_blocks/8),) uint8
    n_live: jax.Array           # () int32
    shape: tuple[int, ...]      # original (pre-flatten) map shape
    m: int                      # flattened rows
    k: int                      # flattened cols
    bs: int
    bc: int
    checksum: jax.Array | None = None   # () uint32, or None (unchecksummed)

    def tree_flatten(self):
        return ((self.payload, self.index, self.n_live, self.checksum),
                (self.shape, self.m, self.k, self.bs, self.bc))

    @classmethod
    def tree_unflatten(cls, aux, children):
        payload, index, n_live, checksum = children
        return cls(payload, index, n_live, *aux, checksum=checksum)

    # --- measured stream accounting (host side; n_live must be concrete) ---
    @property
    def n_blocks(self) -> int:
        return (self.m // self.bs) * (self.k // self.bc)

    @property
    def itemsize(self) -> int:
        return jnp.dtype(self.payload.dtype).itemsize

    def payload_bytes(self) -> int:
        """Bytes of surviving-block data actually in the stream."""
        return int(self.n_live) * self.bs * self.bc * self.itemsize

    def index_bytes(self) -> int:
        return int(self.index.size)       # uint8

    def measured_bytes(self) -> int:
        return self.payload_bytes() + self.index_bytes()

    def dense_bytes(self) -> int:
        return self.m * self.k * self.itemsize

    def zero_frac(self) -> float:
        return 1.0 - int(self.n_live) / max(self.n_blocks, 1)

    def spec(self) -> TokenMapSpec:
        """The analytic MapSpec this stream instantiates (for Eq. 2/3)."""
        return TokenMapSpec(s=self.m, d=self.k, bits=self.itemsize * 8,
                            block_seq=self.bs, block_ch=self.bc)


# ---------------------------------------------------------------------------
# Codec entry points
# ---------------------------------------------------------------------------

def nonzero_bitmap(x: jax.Array, bs: int, bc: int) -> jax.Array:
    """Keep flags for lossless transport of an already-masked map: keep any
    block with at least one nonzero element."""
    M, K = x.shape
    xb = x.reshape(M // bs, bs, K // bc, bc)
    return (jnp.max(jnp.abs(xb), axis=(1, 3)) > 0).astype(jnp.int8)


def compress(x: jax.Array, bitmap: jax.Array | None = None, *, bs: int = 8,
             bc: int = 128, use_kernel: bool = True,
             checksum: bool = False) -> CompressedMap:
    """(..., K) map -> CompressedMap. Leading dims flatten onto M. With no
    bitmap the nonzero-block bitmap is used (always lossless).
    ``checksum=True`` computes the in-band integrity word in-graph
    (``integrity.stream_checksum``) and carries it on the map."""
    shape = tuple(x.shape)
    x2 = x.reshape(-1, shape[-1])
    M, K = x2.shape
    if bitmap is None:
        bitmap = nonzero_bitmap(x2, bs, bc)
    if use_kernel:
        payload, n_live = zebra_pack(x2, bitmap, bs=bs, bc=bc)
    else:
        payload, n_live = ref.zebra_pack_ref(x2, bitmap, bs, bc)
    csum = None
    if checksum:
        from .integrity import stream_checksum
        csum = stream_checksum(payload, bitmap, n_live)
    return CompressedMap(payload=payload, index=pack_bitmap(bitmap),
                         n_live=n_live, shape=shape, m=M, k=K, bs=bs, bc=bc,
                         checksum=csum)


def decompress(cm: CompressedMap, *, use_kernel: bool = True) -> jax.Array:
    bitmap = unpack_bitmap(cm.index, cm.m // cm.bs, cm.k // cm.bc)
    if use_kernel:
        x2 = zebra_unpack(cm.payload, bitmap, bs=cm.bs, bc=cm.bc)
    else:
        x2 = ref.zebra_unpack_ref(cm.payload, bitmap, cm.bs, cm.bc)
    return x2.reshape(cm.shape)


def compress_masked(x: jax.Array, t_obj: float, *, bs: int = 8, bc: int = 128,
                    checksum: bool = False) -> CompressedMap:
    """Streaming lossy codec entry: raw (..., K) map -> Zebra-thresholded
    CompressedMap via the two-phase parallel producer (``zebra_mask_pack``)
    — the dense masked map is never materialized on the way into the
    stream."""
    shape = tuple(x.shape)
    x2 = x.reshape(-1, shape[-1])
    M, K = x2.shape
    payload, bitmap, n_live = zebra_mask_pack(x2, t_obj=t_obj, bs=bs, bc=bc)
    csum = None
    if checksum:
        from .integrity import stream_checksum
        csum = stream_checksum(payload, bitmap, n_live)
    return CompressedMap(payload=payload, index=pack_bitmap(bitmap),
                         n_live=n_live, shape=shape, m=M, k=K, bs=bs, bc=bc,
                         checksum=csum)


def transport_tokens(x: jax.Array, t_obj: float, *, bs: int = 8, bc: int = 128
                     ) -> tuple[jax.Array, jax.Array]:
    """The full inference-site round trip in streaming form:
    ``zebra_mask_pack`` -> ``zebra_unpack`` — only the (payload, bitmap)
    stream between producer and expander. Returns (masked map, keep
    bitmap). Numerically identical to masking alone — but it
    *materializes* the compressed stream, so the serve path observably
    moves compressed bytes when use_kernel is on."""
    shape = tuple(x.shape)
    x2 = x.reshape(-1, shape[-1])
    payload, bitmap, _ = zebra_mask_pack(x2, t_obj=t_obj, bs=bs, bc=bc)
    y2 = zebra_unpack(payload, bitmap, bs=bs, bc=bc)
    return y2.reshape(shape), bitmap


# ---------------------------------------------------------------------------
# Pytree transport (e.g. the prefill -> decode KV-cache handoff)
# ---------------------------------------------------------------------------

def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", getattr(p, "idx", p))))
                    for p in path)


def compress_tree(tree: Any, *, bs: int = 8, bc: int = 128,
                  use_kernel: bool = True, meter=None, site: str = "acts",
                  checksum: bool = False) -> Any:
    """Compress every compatible floating leaf of a pytree (lossless,
    nonzero-block bitmap); incompatible leaves pass through dense. Each leaf
    is recorded on `meter` under "<site>/<path>". ``checksum=True``
    attaches the in-band integrity word per compressed leaf."""
    def one(path, leaf):
        name = f"{site}/{_path_str(path)}"
        dims = None
        if hasattr(leaf, "ndim") and leaf.ndim >= 2 and \
                jnp.issubdtype(leaf.dtype, jnp.floating):
            for nd in (1, 2):
                k = int(np.prod(leaf.shape[-nd:]))
                m = int(np.prod(leaf.shape[:-nd])) if leaf.ndim > nd else 0
                if m and k % bc == 0 and m % bs == 0:
                    dims = (m, k)
                    break
        if dims is None:
            if meter is not None:
                meter.record_dense(name, int(leaf.size) *
                                   jnp.dtype(leaf.dtype).itemsize)
            return leaf
        cm = compress(leaf.reshape(dims), bs=bs, bc=bc, use_kernel=use_kernel,
                      checksum=checksum)
        cm = dataclasses.replace(cm, shape=tuple(leaf.shape))
        if meter is not None:
            meter.record(name, cm)
        return cm

    return jax.tree_util.tree_map_with_path(one, tree)


def decompress_tree(tree: Any, *, use_kernel: bool = True) -> Any:
    return jax.tree_util.tree_map(
        lambda l: decompress(l, use_kernel=use_kernel)
        if isinstance(l, CompressedMap) else l,
        tree, is_leaf=lambda l: isinstance(l, CompressedMap))
