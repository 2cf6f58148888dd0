"""Training semantics for the Pallas Zebra kernels (``jax.custom_vjp``).

The paper trains the block mask and then reaps the bandwidth win at
inference; dynamic feature-map pruning (Liang et al. 2018) and
zero-activation prediction (Shomron et al. 2019) both show the
train-time gating function must match the deployed masking *exactly*.
``zebra_kernel_trainable`` makes that possible on the kernel backends:
the forward is the existing kernel launch (``zebra_mask`` for the
pallas backend, the ``zebra_mask_pack -> zebra_unpack`` stream pair for
the stream backend — the deployed comparator, bit for bit), and the
backward implements the constant-threshold gradient modes of
``core.zebra._apply_gate``:

``hard``  (paper)  dx = g · broadcast(bitmap) — the mask is a 0/1 gate
                   under stop_gradient; only surviving blocks carry the
                   task gradient.
``ste``            dx = g — straight-through identity, so pruned blocks
                   can recover.
``soft``           dx = g · broadcast(sigmoid((blockmax − T_obj)/τ)) —
                   the backward is rescaled by the sigmoid surrogate
                   while the value stays the deployed hard mask.

All three are numerically equal to the reference (pure-jnp) backend in
constant-threshold train mode, so ``jax.grad`` through a pallas/stream
site matches reference bitwise in f32. Sites with a threshold net
(per-sample learned thresholds) are *not* kernel-trainable — the engine
resolves them to reference via the capability registry
(``core.backends``).

Payload order: the stream variant's forward emits and re-expands the
payload in the consumer order of ``kernels.schedule`` (column-grouped
slots). The pipeline here is order-transparent — pack and unpack
address the stream through the same ``slot_map``, so the round trip
(and therefore every gradient mode) is unchanged by the reorder.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .mask_pack import zebra_mask_pack
from .pack import zebra_unpack
from .zebra_mask import block_max, expand_blocks, zebra_mask


class KernelStatics(NamedTuple):
    """Hashable static config for one trainable kernel launch.

    ``variant`` picks the forward: ``"mask"`` (one comparator launch,
    dense masked map out) or ``"stream"`` (the two-phase parallel
    ``zebra_mask_pack`` producer -> ``zebra_unpack``, only the
    compressed stream in between). ``(tm, tk)`` is the comparator
    supertile and ``(gtm, gtk)`` the expander's gather supertile, both
    from ``ZebraConfig.tiles_for`` — every pass tiles under the config
    budget, so no map is ever too big for the producer (the old
    whole-payload-resident design needed a ``fits_vmem`` degrade; the
    two-phase producer does not).
    """
    variant: str
    t_obj: float
    bs: int
    bc: int
    tm: int
    tk: int
    gtm: int
    gtk: int
    pw: int                     # pack-pass slot window (budget-capped)
    grad_mode: str
    soft_temp: float


def _mask_forward(x2: jax.Array, s: KernelStatics):
    y2, bitmap = zebra_mask(x2, t_obj=s.t_obj, bs=s.bs, bc=s.bc,
                            tm=s.tm, tk=s.tk)
    return y2, bitmap, jnp.int32(0)


def _stream_forward(x2: jax.Array, s: KernelStatics):
    payload, bitmap, n_live = zebra_mask_pack(
        x2, t_obj=s.t_obj, bs=s.bs, bc=s.bc, tm=s.tm, tk=s.tk,
        window=s.pw)
    y2 = zebra_unpack(payload, bitmap, bs=s.bs, bc=s.bc, stm=s.gtm,
                      stk=s.gtk)
    return y2, bitmap, n_live


_FORWARD_VARIANTS = {"mask": _mask_forward, "stream": _stream_forward}


def register_forward_variant(name: str, fn) -> None:
    """Add a forward pipeline for a new trainable backend: ``fn(x2,
    statics) -> (y2, bitmap, n_live)``. The backend's BackendSpec names it
    via ``grad_variant``; the custom_vjp backward (gradient modes) is
    shared."""
    _FORWARD_VARIANTS[name] = fn


def has_forward_variant(name: str) -> bool:
    return name in _FORWARD_VARIANTS


def launch_forward(x2: jax.Array, s: KernelStatics):
    """The ONE forward kernel pipeline shared by train (custom_vjp fwd)
    and infer (engine dispatch) — train and infer cannot drift apart.
    Returns (y2, bitmap, n_live); n_live is 0 for the mask variant."""
    try:
        fwd = _FORWARD_VARIANTS[s.variant]
    except KeyError:
        raise ValueError(
            f"unknown trainable kernel variant {s.variant!r}; expected one "
            f"of {tuple(_FORWARD_VARIANTS)} (register_forward_variant)"
        ) from None
    return fwd(x2, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def zebra_kernel_trainable(x2: jax.Array, statics: KernelStatics):
    """Kernel-launched Zebra site with training semantics.

    x2 (M, K) -> (masked y2 (M, K), keep bitmap int8, n_live int32).
    Forward is the real kernel dispatch; ``jax.grad`` takes the
    ``statics.grad_mode`` backward (see module docstring). The bitmap
    and n_live outputs are non-differentiable observables.
    """
    return launch_forward(x2, statics)


def _fwd(x2, statics):
    out = launch_forward(x2, statics)
    if statics.grad_mode == "soft":
        res = x2                       # recompute blockmax for the surrogate
    elif statics.grad_mode == "ste":
        res = None
    else:                              # hard (paper default)
        res = out[1]
    return out, res


def _bwd(statics, res, cts):
    gy = cts[0]
    if statics.grad_mode == "ste":
        return (gy,)
    if statics.grad_mode == "soft":
        blockmax = block_max(res, statics.bs, statics.bc)
        thr = jnp.asarray(statics.t_obj, blockmax.dtype)
        gate = jax.nn.sigmoid((blockmax - thr) / statics.soft_temp)
        return (gy * expand_blocks(gate, statics.bs, statics.bc
                                   ).astype(gy.dtype),)
    mask = expand_blocks(res, statics.bs, statics.bc).astype(gy.dtype)
    return (gy * mask,)


zebra_kernel_trainable.defvjp(_fwd, _bwd)
