"""Where the Pallas kernels run: compiled by Mosaic on a TPU, or through
the Pallas interpreter everywhere else (the CPU test containers).

This is the ONE place that decision is made; no kernel, config or
caller carries an ``interpret`` option, so a TPU run can never end up
timing the interpreter.
"""
from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    """True when Pallas must interpret its kernels: the default backend
    is not a TPU. Read at trace time by every ``pallas_call`` and by the
    choice between each kernel's TPU form and its XLA form."""
    return jax.default_backend() != "tpu"


def tpu_tileable(bs: int, bc: int) -> bool:
    """Whether ``(bs, bc)`` Zebra blocks cover whole (8, 128) vreg tiles —
    the block shape every Pallas TPU form of the Zebra kernels addresses.
    On a TPU the engine resolves sites with narrower blocks (the paper's
    4x4 NCHW blocks on 4-wide lanes) to its reference path, labelled
    ``reference(narrow-blocks)``; off a TPU they run the kernels
    interpreted."""
    return bs % 8 == 0 and bc % 128 == 0


def tpu_forms(bs: int, bc: int) -> bool:
    """Whether the Pallas TPU forms (payload-window GEMM, gather-pack,
    windowed expander) run for ``(bs, bc)`` blocks: compiled on a TPU
    with tileable blocks. Elsewhere the XLA forms of the same contracts
    run — faster than those kernels under the interpreter, bit for bit."""
    return not pallas_interpret() and tpu_tileable(bs, bc)
