"""Matrix products at a stated precision, for the references.

``"f32"`` is float32 at ``Precision.HIGHEST`` (on a TPU a float32
product otherwise runs in bfloat16 passes). The lower modes exist for the
controls: ``"bf16"`` rounds both operands to bfloat16, ``"fp8"`` rounds
them to float8 e4m3 with one scale per operand (its largest magnitude
maps to 448, the format's largest value); both then multiply exactly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("f32", "bf16", "fp8")
HIGHEST = jax.lax.Precision.HIGHEST


def rounded(x, mode: str):
    if mode == "f32":
        return x
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "fp8":
        s = jnp.max(jnp.abs(x)) / 448.0
        s = jnp.where(s > 0, s, 1.0)
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown precision mode {mode!r}")


def einsum(eq: str, a, b, mode: str = "f32"):
    return jnp.einsum(eq, rounded(a.astype(jnp.float32), mode),
                      rounded(b.astype(jnp.float32), mode),
                      precision=HIGHEST)


def conv(x, w, stride: int, mode: str = "f32"):
    return jax.lax.conv_general_dilated(
        rounded(x, mode), rounded(w, mode), (stride, stride), "SAME",
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)
