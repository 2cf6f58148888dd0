"""Paged pool of compressed KV payload slabs.

The serving engine keeps only the in-flight lanes' caches dense (the
"hot" working set); everything else — freshly prefilled requests on
their way into a lane, and requests evicted under slot pressure — lives
here as Zebra ``(bitmap, payload)`` streams. A page is ``page_tokens``
consecutive cache positions of one leaf, flattened to ``(rows, Hkv*hd)``
exactly like ``attention.zebra_kv_site`` lays the cache out on the wire,
and compressed with the PR 3/5 payload-across-jit handoff primitive
(``compress.stream``): the pool IS the transport, so every page is
metered on the shared ``BandwidthMeter`` (Eq. 2/3 reconciliation per
page) and validated at ingest via ``compress.integrity`` — a corrupt
page degrades to a dense page, never the whole request.

Block sizing follows the ``ffn.eff_block_ch`` fallback idiom: reduced
configs whose ``Hkv*hd`` doesn't divide ``zebra_block_ch`` compress at
``bc = Hkv*hd`` instead of passing through dense — the stream stays a
stream at every scale.

Leaves without a page-divisible token axis (recurrent state, odd
shapes) are stored dense and metered as dense traffic.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..compress import BandwidthMeter, CompressedMap, compress, decompress
from ..compress.integrity import validate_level, validate_map
from ..ft.faults import CorruptStream
from ..ft.inject import STREAM_KINDS, active_plan, corrupt_map

PAGE_SITE = "page"          # ft.inject site label for page-ingest chaos


class _Slab:
    """One request's paged store: per-leaf page lists + reassembly info."""

    def __init__(self, treedef):
        self.treedef = treedef
        self.leaves: list[tuple[str, Any]] = []   # ("paged", [...]) | ("dense", arr)
        self.page_shapes: list[tuple[int, ...] | None] = []


class PagedKVPool:
    """Compressed page-in/page-out store keyed by request id.

    ``page_out(rid, caches)`` replaces any previous slab for ``rid`` —
    the stream is re-emitted (and re-metered: eviction traffic is real
    traffic). ``page_in(rid)`` decompresses the slab back to the dense
    per-request tree, bitwise-equal to what was paged out (modulo pages
    that failed ingest validation, which were kept dense and are
    therefore trivially bitwise-equal too).
    """

    def __init__(self, *, page_tokens: int = 16, bs: int = 8, bc: int = 128,
                 validation: str = "off", use_kernel: bool = False,
                 breaker=None):
        if page_tokens & (page_tokens - 1) or page_tokens < 1:
            raise ValueError(f"page_tokens must be a power of two, got {page_tokens}")
        self.page_tokens = page_tokens
        self.bs, self.bc = bs, bc
        self.validation = validate_level(validation)
        self.use_kernel = use_kernel
        self.breaker = breaker    # ft.breaker.BreakerBoard | None — the
                                  # page-ingest circuit: open means pages
                                  # skip compress+validate wholesale
        self.meter = BandwidthMeter()
        self._slabs: dict[Any, _Slab] = {}
        # jitted codecs keyed on (shape, dtype): after warmup every page
        # op is one cached dispatch — the page path never retraces
        self._enc: dict = {}
        self._dec: dict = {}
        self.n_pages_out = 0
        self.n_pages_in = 0
        self.n_recovered = 0      # corrupt pages kept dense at ingest
        self.n_breaker_dense = 0  # pages sent dense by an OPEN breaker
        self.bytes_out = 0        # stream bytes written to the pool
        self.bytes_in = 0         # stream bytes read back out

    # ------------------------------------------------------------------
    def _eff_blocks(self, m: int, k: int) -> tuple[int, int]:
        """eff_block_ch-style divisor fallback so pages compress even
        when the reduced head dims don't divide the configured blocks."""
        bs = self.bs if m % self.bs == 0 else 1
        bc = self.bc if k % self.bc == 0 else k
        return bs, bc

    def _encode(self, page2d: jax.Array) -> CompressedMap:
        key = (tuple(page2d.shape), str(page2d.dtype))
        fn = self._enc.get(key)
        if fn is None:
            bs, bc = self._eff_blocks(*page2d.shape)
            fn = jax.jit(functools.partial(
                compress, bs=bs, bc=bc, use_kernel=self.use_kernel,
                checksum=(self.validation == "checksum")))
            self._enc[key] = fn
        return fn(page2d)

    def _decode(self, cm: CompressedMap) -> jax.Array:
        key = (tuple(cm.payload.shape), str(cm.payload.dtype), cm.bs, cm.bc)
        fn = self._dec.get(key)
        if fn is None:
            fn = jax.jit(functools.partial(
                decompress, use_kernel=self.use_kernel))
            self._dec[key] = fn
        return fn(cm)

    @staticmethod
    def _pageable(leaf) -> bool:
        """Attn cache leaves: (..., B, T, Hkv, hd) with T at axis -3 (the
        model_prefill_pad convention)."""
        return (hasattr(leaf, "ndim") and leaf.ndim >= 4
                and jnp.issubdtype(leaf.dtype, jnp.floating))

    # ------------------------------------------------------------------
    def page_out(self, rid, caches) -> None:
        """Compress a per-request cache tree into the slab store. The
        ingest boundary: an armed chaos plan (``ft.inject``) with a
        stream fault at site ``"page"`` corrupts pages here — after
        compression, before validation — and a page that fails
        ``validate_map`` is kept dense (per-page fallback)."""
        leaves, treedef = jax.tree_util.tree_flatten(caches)
        slab = _Slab(treedef)
        plan = active_plan()
        pt = self.page_tokens
        for i, leaf in enumerate(leaves):
            T = leaf.shape[-3] if self._pageable(leaf) else 0
            if not T or T % pt:
                slab.leaves.append(("dense", jnp.asarray(leaf)))
                slab.page_shapes.append(None)
                nbytes = int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
                self.meter.record_dense(f"req{rid}/leaf{i}", nbytes)
                self.bytes_out += nbytes
                continue
            k = int(np.prod(leaf.shape[-2:]))
            pages = []
            page_shape = leaf.shape[:-3] + (pt,) + leaf.shape[-2:]
            ax = leaf.ndim - 3
            for p in range(T // pt):
                page = jax.lax.slice_in_dim(leaf, p * pt, (p + 1) * pt, axis=ax)
                name = f"req{rid}/leaf{i}/pg{p}"
                if self.breaker is not None \
                        and not self.breaker.allow(PAGE_SITE):
                    # circuit OPEN: the compressed path at this boundary
                    # is sick — dense wholesale, skipping compress AND
                    # the per-page validate+fallback entirely (armed
                    # chaos faults stay armed: nothing fires on a path
                    # that never runs)
                    dense = jnp.asarray(page)
                    pages.append(dense)
                    nbytes = int(dense.size) * dense.dtype.itemsize
                    self.meter.record_dense(f"{name}+breaker-open", nbytes)
                    self.bytes_out += nbytes
                    self.n_breaker_dense += 1
                    continue
                cm = self._encode(page.reshape(-1, k))
                if plan is not None:
                    f = plan.take(STREAM_KINDS, PAGE_SITE)
                    if f is not None:
                        cm = corrupt_map(cm, f.kind, arg=f.arg)
                        plan.note(f.kind, PAGE_SITE)
                try:
                    validate_map(cm, level=self.validation,
                                 site=f"{PAGE_SITE}:{name}")
                except CorruptStream as e:
                    # per-page dense fallback: ONE page degrades, the
                    # request's other pages stay compressed — and the
                    # breaker counts the detection toward its trip window
                    if self.breaker is not None:
                        self.breaker.record_failure(PAGE_SITE)
                    self.n_recovered += 1
                    print(f"[pool] {e} — page kept dense")
                    dense = jnp.asarray(page)
                    pages.append(dense)
                    nbytes = int(dense.size) * dense.dtype.itemsize
                    self.meter.record_dense(name, nbytes)
                    self.bytes_out += nbytes
                    continue
                if self.breaker is not None and self.validation != "off":
                    self.breaker.record_success(PAGE_SITE)
                rec = self.meter.record(name, cm)
                self.bytes_out += rec.measured_bytes
                self.n_pages_out += 1
                pages.append(cm)
            slab.leaves.append(("paged", pages))
            slab.page_shapes.append(page_shape)
        self._slabs[rid] = slab

    def page_in(self, rid):
        """Slab -> dense per-request cache tree (bitwise round trip)."""
        slab = self._slabs[rid]
        out = []
        for (kind, stored), pshape in zip(slab.leaves, slab.page_shapes):
            if kind == "dense":
                out.append(stored)
                self.bytes_in += int(stored.size) * stored.dtype.itemsize
                continue
            parts = []
            for page in stored:
                if isinstance(page, CompressedMap):
                    parts.append(self._decode(page).reshape(pshape))
                    self.bytes_in += page.measured_bytes()
                    self.n_pages_in += 1
                else:                      # dense-fallback page
                    parts.append(page)
                    self.bytes_in += int(page.size) * page.dtype.itemsize
            out.append(jnp.concatenate(parts, axis=len(pshape) - 3))
        return jax.tree_util.tree_unflatten(slab.treedef, out)

    # ------------------------------------------------------------------
    def free(self, rid) -> None:
        self._slabs.pop(rid, None)

    def __contains__(self, rid) -> bool:
        return rid in self._slabs

    def request_bytes(self, rid) -> dict:
        """Per-request KV traffic: measured stream bytes vs the Eq. 2/3
        prediction at each page's measured zero fraction vs dense, plus
        the compressed-page count (the index-padding reconcile bound
        scales with it)."""
        prefix = f"req{rid}/"
        recs = [r for r in self.meter.records if r.site.startswith(prefix)]
        return {
            "measured": sum(r.measured_bytes for r in recs),
            "predicted": sum(r.predicted_bytes for r in recs),
            "dense": sum(r.dense_bytes for r in recs),
            "pages": sum(1 for r in recs if r.compressed),
        }

    def zero_frac(self) -> float:
        """Block-weighted zero fraction across every compressed page."""
        live = sum(r.n_live for r in self.meter.records)
        blocks = sum(r.n_blocks for r in self.meter.records)
        return 1.0 - live / blocks if blocks else 0.0
