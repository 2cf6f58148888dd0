"""Zebra — Zero-Block Regularization of activation maps (Shih & Chang, ISCAS'20).

The paper's contribution, as a composable JAX module.

Two activation layouts are supported:

* **CNN maps** ``(B, C, H, W)`` — faithful reproduction: non-overlapping
  spatial ``b×b`` blocks per channel, block importance = block max, one
  threshold per (layer, channel) produced by a GAP+FC threshold network
  (training) or the constant ``T_obj`` (inference). Paper §II.A/§II.B.
* **Token maps** ``(B, S, D)`` — the TPU adaptation (DESIGN.md §2): blocks
  are ``(block_seq × block_ch)`` tiles, shaped like VMEM tiles so that a
  zero block is a skippable HBM transfer. Importance uses ``max(|x|)``
  because RMSNorm'd activations are unbounded/signed (post-ReLU maps are
  non-negative, where ``max(|x|) == max(x)`` — so the CNN path stays
  faithful).

Training-mode gradient semantics (paper-faithful default ``grad_mode=
"hard"``): the mask is a hard 0/1 gate under ``stop_gradient``; thresholds
receive gradient *only* from the L2 regularizer pulling them to ``T_obj``
(Eq. 1), surviving blocks receive the task gradient. ``"ste"`` and
``"soft"`` are beyond-paper trainability variants.

Constant-threshold training (``tnet=None`` in train mode, or
``use_tnet=False``): the deployed ``T_obj`` comparator is the forward
gate for *all* gradient modes — the mode only selects the backward
surrogate — so train-time gating matches inference masking exactly.
This is the semantics the kernel backends reproduce via ``custom_vjp``
(``kernels.grad``); the reg slot reports the realized zero-block count.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

Aux = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class ZebraConfig:
    enabled: bool = True
    t_obj: float = 0.1           # target threshold T_obj (Eq. 1), in [0, 1]
    block_hw: int = 4            # spatial b for CNN maps (paper: 4 / 8 / 2)
    block_seq: int = 8           # token-block rows for LM maps (VMEM sublane)
    block_ch: int = 128          # channel-block cols for LM maps (VMEM lane)
    lambda_ce: float = 1.0       # λ weighting the CE term in Eq. 1
    mode: str = "train"          # "train" (threshold net) | "infer" (T_obj)
    grad_mode: str = "hard"      # "hard" (paper) | "ste" | "soft"
    soft_temp: float = 0.05
    use_tnet: bool = True        # train with a learned threshold net; False
                                 # = constant-T_obj (deployment-matched)
                                 # training, which the kernel backends can
                                 # serve through jax.custom_vjp
    act_bits: int = 16           # B in Eq. 2 (bf16 activations on TPU)
    # --- site-engine execution (core.engine) ---
    backend: str = "reference"   # reference | pallas | stream | fused
    site_backends: tuple[tuple[str, str], ...] = ()  # per-site overrides
    vmem_budget_bytes: int = 8 * 1024 * 1024
                                 # per-launch VMEM working-set cap the tile
                                 # chooser (tiles_for) sizes comparator
                                 # tiles AND GEMM/gather supertiles against
                                 # (~half a 16 MB core)
    zero_frac_hint: float | None = None
                                 # expected zero-block fraction at this
                                 # site (e.g. the paper's ~0.64 operating
                                 # point). Threaded into the cached
                                 # gemm_plan chooser, where it tightens
                                 # the scheduled consumers' capacity
                                 # ladder; never changes kernel-form
                                 # supertiles (numerics stay hint-free)
    validation: str = "off"      # stream-integrity level at every boundary
                                 # that consumes a (bitmap, payload) stream
                                 # (compress.integrity): "off" (hot path
                                 # untouched) | "structural" (popcount /
                                 # finite / live-slot invariants +
                                 # recompute-from-dense recovery) |
                                 # "checksum" (+ uint32 fold carried
                                 # in-band, catches finite value flips)

    def __post_init__(self):
        # config-time validation against the capability registry — a typo'd
        # backend fails where the config is built, not at first dispatch
        from .backends import validate_backend
        from ..compress.integrity import validate_level
        if self.backend:
            validate_backend(self.backend)
        for _, name in self.site_backends:
            if name:
                validate_backend(name)
        validate_level(self.validation)

    def replace(self, **kw) -> "ZebraConfig":
        return dataclasses.replace(self, **kw)

    def backend_for(self, site: str = "") -> str:
        """Resolve the execution backend for one named site."""
        return dict(self.site_backends).get(site, self.backend) or "reference"

    def tiles_for(self, M: int, K: int, bs: int, bc: int, dtype, *,
                  kind: str = "comparator", n: int | None = None):
        """VMEM-budget/dtype-aware supertile chooser for an (M, K) map
        with (bs, bc) Zebra blocks — the ONE tiling policy every kernel
        launch goes through, so producers and consumers cannot disagree.

        ``kind="comparator"`` (default): tile (tm, tk) for the bitmap /
        masking passes. The pass holds an input tile and an output tile
        in VMEM (2 * tm * tk * itemsize bytes; the bitmap tile is
        negligible), so the chooser takes the widest block-aligned tk
        that leaves at least one block row within ``vmem_budget_bytes``,
        then the tallest block-aligned tm that fits — bf16 maps get
        twice the f32 tile. Never shrinks below one (bs, bc) block; XLA
        pads sub-tile maps.

        ``kind="gemm"``: GEMM supertile (stm, stk, bn) for the
        block-skipping consumers (``zebra_spmm`` / ``zebra_spmm_cs``)
        against a (K, ``n``) weight — block-count divisors of the map
        sides (no ragged payload windows) capped per step, accounting
        for the activation windows, the (stk, bn) weight window and the
        fp32 accumulator/output under the same budget. Routed through
        the cached ``supertile.gemm_plan`` chooser (with
        ``zero_frac_hint``), so repeated site launches hit the plan
        cache; the engine's fused path reads the full plan (including
        the scheduled capacity ladder) via ``gemm_plan_for``.

        ``kind="gather"``: supertile (stm, stk) for the payload
        expander (``zebra_unpack``).
        """
        from ..kernels import supertile as st
        item = jnp.dtype(dtype).itemsize
        if kind == "gemm":
            plan = self.gemm_plan_for(M, K, bs, bc, dtype, n=n)
            return plan.stm, plan.stk, plan.bn
        if kind == "gather":
            return st.gather_supertiles(M, K, bs, bc, item,
                                        int(self.vmem_budget_bytes))
        if kind != "comparator":
            raise ValueError(f"unknown tile kind {kind!r}")
        return st.comparator_tiles(M, K, bs, bc, item,
                                   int(self.vmem_budget_bytes))

    def gemm_plan_for(self, M: int, K: int, bs: int, bc: int, dtype, *,
                      n: int | None = None):
        """The full cached GEMM plan (kernel-form supertile + the
        scheduled consumers' capacity ladder) for an (M, K) x (K, n)
        site under this config's budget and ``zero_frac_hint``."""
        from ..kernels import supertile as st
        if n is None:
            raise ValueError("kind='gemm' needs the weight width n")
        return st.gemm_plan(M, K, n, bs, bc, jnp.dtype(dtype).itemsize,
                            int(self.vmem_budget_bytes),
                            zero_frac=self.zero_frac_hint)


# ---------------------------------------------------------------------------
# Threshold network: T_{l,c} = FC(GAP(x))  (paper Fig. 2)
# ---------------------------------------------------------------------------

def init_threshold_net(key: jax.Array, channels: int, dtype=jnp.float32) -> dict:
    """One per Zebra site. FC maps GAP features -> per-channel thresholds."""
    w = jax.random.normal(key, (channels, channels), dtype) * (channels ** -0.5)
    b = jnp.zeros((channels,), dtype)
    return {"w": w, "b": b}


def _thresholds_from_net(tnet: dict, gap: jax.Array) -> jax.Array:
    """gap: (B, C) -> per-sample, per-channel thresholds (B, C)."""
    return gap @ tnet["w"] + tnet["b"]


def init_token_threshold_net(key: jax.Array, d: int, n_ch_blocks: int,
                             dtype=jnp.float32) -> dict:
    """LM variant (DESIGN.md §2): the FC emits one threshold per *channel
    block* (d_ff can be 22k wide — a C×C FC would be 0.5B params/layer)."""
    w = jax.random.normal(key, (d, n_ch_blocks), dtype) * (d ** -0.5)
    b = jnp.zeros((n_ch_blocks,), dtype)
    return {"w": w, "b": b}


# ---------------------------------------------------------------------------
# Block partition + masking
# ---------------------------------------------------------------------------

def _block_reduce_max_nchw(x: jax.Array, b: int) -> jax.Array:
    """(B,C,H,W) -> per-block max|x| (B,C,H//b,W//b). H,W must divide by b.

    One windowed max, which XLA fuses with the threshold compare that
    reads it: no block max is written."""
    return jax.lax.reduce_window(jnp.abs(x), -jnp.inf, jax.lax.max,
                                 (1, 1, b, b), (1, 1, b, b), "VALID")


def _block_reduce_max_nchw_grad(x: jax.Array, b: int) -> jax.Array:
    """``_block_reduce_max_nchw`` as a reshape and ``jnp.max``, whose
    gradient splits a tied block's among its ties (the windowed max sends
    it to one element)."""
    B, C, H, W = x.shape
    xb = x.reshape(B, C, H // b, b, W // b, b)
    return jnp.max(jnp.abs(xb), axis=(3, 5))


def _block_reduce_max_bsd(x: jax.Array, bs: int, bc: int) -> jax.Array:
    """(B,S,D) -> per-block max (B,S//bs,D//bc)."""
    B, S, D = x.shape
    xb = x.reshape(B, S // bs, bs, D // bc, bc)
    return jnp.max(jnp.abs(xb), axis=(2, 4))


def _expand_mask_nchw(mask_blocks: jax.Array, b: int) -> jax.Array:
    """(B,C,Hb,Wb) -> (B,C,Hb*b,Wb*b), each value over its b x b block:
    one broadcast to the map's shape."""
    B, C, Hb, Wb = mask_blocks.shape
    m = jnp.broadcast_to(mask_blocks[:, :, :, None, :, None],
                         (B, C, Hb, b, Wb, b))
    return m.reshape(B, C, Hb * b, Wb * b)


def _expand_mask_nchw_grad(mask_blocks: jax.Array, b: int) -> jax.Array:
    """``_expand_mask_nchw`` as two ``jnp.repeat``s, whose gradient sums
    each block row first, then the rows."""
    m = jnp.repeat(mask_blocks, b, axis=2)
    return jnp.repeat(m, b, axis=3)


def _expand_mask_bsd(mask_blocks: jax.Array, bs: int, bc: int) -> jax.Array:
    m = jnp.repeat(mask_blocks, bs, axis=1)
    return jnp.repeat(m, bc, axis=2)


def _apply_gate(x: jax.Array, keep: jax.Array, blockmax: jax.Array,
                thr: jax.Array, cfg: ZebraConfig, expand,
                surrogate_only: bool = False) -> jax.Array:
    """Gate x by the block keep-mask under the configured gradient mode.

    ``surrogate_only`` (constant-threshold / deployment-matched training):
    the *value* is always the deployed hard mask — the gradient mode only
    picks the backward surrogate, so the train-time gating function is
    exactly the inference comparator (and exactly what the kernel
    backends' custom_vjp computes, see ``kernels.grad``).
    """
    if cfg.grad_mode == "soft" and cfg.mode == "train":
        gate = jax.nn.sigmoid((blockmax - thr) / cfg.soft_temp)
        if surrogate_only:
            # value: hard mask; dy/dx: the sigmoid surrogate gate
            mask = expand(jax.lax.stop_gradient(keep)).astype(x.dtype)
            ge = expand(jax.lax.stop_gradient(gate)).astype(x.dtype)
            return x * ge + jax.lax.stop_gradient(x * mask - x * ge)
        return x * expand(gate).astype(x.dtype)
    mask = expand(jax.lax.stop_gradient(keep)).astype(x.dtype)
    y = x * mask
    if cfg.grad_mode == "ste" and cfg.mode == "train":
        # value: masked; gradient wrt x: identity (lets pruned blocks recover)
        y = y + (x - jax.lax.stop_gradient(x)) * (1.0 - mask)
    return y


def _reg_loss(thr: jax.Array, t_obj: float) -> jax.Array:
    """Σ_c ||T_obj − T_c||², averaged over the batch dim (Eq. 1 second term)."""
    per_sample = jnp.sum(jnp.square(t_obj - thr.astype(jnp.float32)), axis=-1)
    return jnp.mean(per_sample)


def effective_tnet(cfg: ZebraConfig, tnet):
    """``use_tnet=False`` is authoritative: gate with the constant T_obj
    even if legacy net params are passed (their Eq. 1 L2 term is excluded
    from the loss in that mode, so gating with them would silently train
    un-regularized thresholds)."""
    return tnet if cfg.use_tnet else None


def require_tnet(cfg: ZebraConfig, tnet, site: str = "") -> None:
    """Train mode with ``use_tnet=True`` must receive threshold-net params:
    silently training the constant-T_obj gate instead would change the
    objective. The ONE guard shared by zebra_cnn/zebra_tokens and the
    engine."""
    if cfg.mode == "train" and tnet is None and cfg.use_tnet:
        at = f" at site {site!r}" if site else ""
        raise ValueError(
            f"train mode expects threshold-net params{at} (use_tnet=True); "
            f"pass tnet, or set use_tnet=False for constant-threshold "
            f"(kernel-trainable) training")


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def zebra_cnn(x: jax.Array, cfg: ZebraConfig, tnet: dict | None = None) -> tuple[jax.Array, Aux]:
    """Zebra over a (B, C, H, W) activation map. Returns (masked x, aux).

    aux: reg (scalar), zero_frac (scalar in [0,1]), n_blocks, thresholds.
    """
    if not cfg.enabled:
        return x, {"reg": jnp.float32(0.0), "zero_frac": jnp.float32(0.0),
                   "n_blocks": 0, "thresholds": None}
    B, C, H, W = x.shape
    b = cfg.block_hw
    if H % b or W % b:
        raise ValueError(f"map {H}x{W} not divisible by block {b}")
    tnet = effective_tnet(cfg, tnet)
    require_tnet(cfg, tnet)
    # The soft gate differentiates through the block max and its
    # expansion: it keeps the reshape/max and repeat forms and their
    # gradients (tied maxima share one; block sums row by row).
    if cfg.grad_mode == "soft" and cfg.mode == "train":
        block_max, expand = _block_reduce_max_nchw_grad, _expand_mask_nchw_grad
    else:
        block_max, expand = _block_reduce_max_nchw, _expand_mask_nchw
    blockmax = block_max(x, b)                                    # (B,C,Hb,Wb)
    surrogate_only = False
    if cfg.mode == "train" and tnet is not None:
        gap = jnp.mean(x, axis=(2, 3)).astype(jnp.float32)        # (B,C) GAP
        thr = _thresholds_from_net(tnet, gap)                     # (B,C)
        reg = _reg_loss(thr, cfg.t_obj)
        thr_b = thr[:, :, None, None].astype(blockmax.dtype)
    else:
        # infer, or constant-threshold (deployment-matched) training: the
        # deployed T_obj comparator is the gate (Fig. 3); in train mode the
        # reg slot reports the realized zero-block count (Eq. 1 observable)
        thr = jnp.full((C,), cfg.t_obj, jnp.float32)
        reg = None if cfg.mode == "train" else jnp.float32(0.0)
        thr_b = thr[None, :, None, None].astype(blockmax.dtype)
        surrogate_only = cfg.mode == "train"
    keep = (blockmax >= thr_b)
    y = _apply_gate(x, keep, blockmax, thr_b, cfg,
                    lambda m: expand(m, b), surrogate_only)
    zero_frac = 1.0 - jnp.mean(keep.astype(jnp.float32))
    n_blocks = C * (H // b) * (W // b)
    if reg is None:
        reg = jax.lax.stop_gradient(zero_frac) * n_blocks
    return y, {"reg": reg, "zero_frac": zero_frac, "n_blocks": n_blocks,
               "thresholds": thr}


def zebra_tokens(x: jax.Array, cfg: ZebraConfig, tnet: dict | None = None) -> tuple[jax.Array, Aux]:
    """Zebra over a (B, S, D) token activation map (TPU tile blocks)."""
    if not cfg.enabled:
        return x, {"reg": jnp.float32(0.0), "zero_frac": jnp.float32(0.0),
                   "n_blocks": 0, "thresholds": None}
    B, S, D = x.shape
    bs, bc = cfg.block_seq, cfg.block_ch
    if S % bs or D % bc:
        raise ValueError(f"(S={S}, D={D}) not divisible by block ({bs},{bc})")
    tnet = effective_tnet(cfg, tnet)
    require_tnet(cfg, tnet)
    blockmax = _block_reduce_max_bsd(x, bs, bc)                   # (B,Sb,Db)
    surrogate_only = False
    if cfg.mode == "train" and tnet is not None:
        gap = jnp.mean(jnp.abs(x), axis=1).astype(jnp.float32)    # (B,D) GAP
        thr_ch = _thresholds_from_net(tnet, gap)                  # (B,Db)
        reg = _reg_loss(thr_ch, cfg.t_obj)
        thr_b = thr_ch[:, None, :].astype(blockmax.dtype)         # (B,1,Db)
    else:
        # infer, or constant-threshold (deployment-matched) training
        reg = None if cfg.mode == "train" else jnp.float32(0.0)
        thr_b = jnp.asarray(cfg.t_obj, blockmax.dtype)
        thr_ch = None
        surrogate_only = cfg.mode == "train"
    keep = (blockmax >= thr_b)
    y = _apply_gate(x, keep, blockmax, thr_b, cfg,
                    lambda m: _expand_mask_bsd(m, bs, bc), surrogate_only)
    zero_frac = 1.0 - jnp.mean(keep.astype(jnp.float32))
    n_blocks = (S // bs) * (D // bc)
    if reg is None:
        reg = jax.lax.stop_gradient(zero_frac) * n_blocks
    return y, {"reg": reg, "zero_frac": zero_frac, "n_blocks": n_blocks,
               "thresholds": thr_ch}


def zebra_infer_bitmap_nchw(x: jax.Array, cfg: ZebraConfig) -> tuple[jax.Array, jax.Array]:
    """Inference helper: (masked x, keep-bitmap) for hardware-style storage.

    Like ``zebra_cnn``, ``cfg.enabled=False`` is a passthrough: x unchanged,
    every block kept (all-ones bitmap).
    """
    b = cfg.block_hw
    B, C, H, W = x.shape
    if not cfg.enabled:
        return x, jnp.ones((B, C, H // b, W // b), bool)
    blockmax = _block_reduce_max_nchw(x, b)
    keep = blockmax >= jnp.asarray(cfg.t_obj, blockmax.dtype)
    y = x * _expand_mask_nchw(keep, b).astype(x.dtype)
    return y, keep


def zebra_infer_bitmap_tokens(x: jax.Array, cfg: ZebraConfig) -> tuple[jax.Array, jax.Array]:
    bs, bc = cfg.block_seq, cfg.block_ch
    B, S, D = x.shape
    if not cfg.enabled:
        return x, jnp.ones((B, S // bs, D // bc), bool)
    blockmax = _block_reduce_max_bsd(x, bs, bc)
    keep = blockmax >= jnp.asarray(cfg.t_obj, blockmax.dtype)
    y = x * _expand_mask_bsd(keep, bs, bc).astype(x.dtype)
    return y, keep


def collect_zebra_loss(auxes: list[Aux]) -> jax.Array:
    """Σ_{l} reg_l — the second term of Eq. 1 across all Zebra sites."""
    regs = [a["reg"] for a in auxes if a.get("reg") is not None]
    return jnp.sum(jnp.stack(regs)) if regs else jnp.float32(0.0)


def mean_zero_frac(auxes: list[Aux]) -> jax.Array:
    """Block-count-weighted mean zero-block fraction across sites."""
    num, den = jnp.float32(0.0), 0.0
    for a in auxes:
        nb = float(a.get("n_blocks", 0) or 0)
        if nb:
            num = num + a["zero_frac"] * nb
            den += nb
    return num / den if den else jnp.float32(0.0)
