"""Shared NN layers (functional, pytree params). CNN side uses NCHW (paper
convention); LM side uses (B, S, D).

Every layer is an (init, apply) pair. BatchNorm keeps running stats in a
separate `state` tree so `apply` stays pure.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ----------------------------------------------------------------------------
# Initializers
# ----------------------------------------------------------------------------

def he_normal(key, shape, dtype=jnp.float32, fan_in=None):
    if fan_in is None:
        fan_in = int(np.prod(shape[1:])) if len(shape) > 1 else shape[0]
    return jax.random.normal(key, shape, dtype) * float(np.sqrt(2.0 / fan_in))


def lecun_normal(key, shape, dtype=jnp.float32, fan_in=None):
    if fan_in is None:
        fan_in = int(np.prod(shape[1:])) if len(shape) > 1 else shape[0]
    # a Python float scale keeps ``dtype`` (a numpy scalar would promote
    # bf16 weights to f32 and double their HBM footprint)
    return jax.random.normal(key, shape, dtype) * float(np.sqrt(1.0 / fan_in))


# ----------------------------------------------------------------------------
# Conv2D (NCHW / OIHW)
# ----------------------------------------------------------------------------

def conv_init(key, c_in, c_out, k, dtype=jnp.float32, groups: int = 1):
    w = he_normal(key, (c_out, c_in // groups, k, k), dtype,
                  fan_in=(c_in // groups) * k * k)
    return {"w": w}


def conv_apply(p, x, stride: int = 1, padding="SAME", groups: int = 1):
    return jax.lax.conv_general_dilated(
        x, p["w"].astype(x.dtype),
        window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups)


# ----------------------------------------------------------------------------
# BatchNorm (NCHW, per-channel)
# ----------------------------------------------------------------------------

def bn_init(c, dtype=jnp.float32):
    params = {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}
    state = {"mean": jnp.zeros((c,), jnp.float32), "var": jnp.ones((c,), jnp.float32)}
    return params, state


def bn_apply(p, s, x, train: bool, momentum: float = 0.9, eps: float = 1e-5):
    """Returns (y, new_state)."""
    if train:
        mean = jnp.mean(x, axis=(0, 2, 3))
        var = jnp.var(x, axis=(0, 2, 3))
        new_s = {"mean": momentum * s["mean"] + (1 - momentum) * mean.astype(jnp.float32),
                 "var": momentum * s["var"] + (1 - momentum) * var.astype(jnp.float32)}
    else:
        mean, var = s["mean"], s["var"]
        new_s = s
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    y = (x - mean[None, :, None, None].astype(x.dtype)) * inv[None, :, None, None].astype(x.dtype)
    y = y * p["scale"][None, :, None, None].astype(x.dtype) + p["bias"][None, :, None, None].astype(x.dtype)
    return y, new_s


# ----------------------------------------------------------------------------
# Dense
# ----------------------------------------------------------------------------

def dense_init(key, d_in, d_out, dtype=jnp.float32, bias=True, init=he_normal):
    p = {"w": init(key, (d_in, d_out), dtype, fan_in=d_in)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p, x):
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# ----------------------------------------------------------------------------
# Pooling
# ----------------------------------------------------------------------------

def max_pool(x, k=2, stride=2):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, k, k), (1, 1, stride, stride), "VALID")


def avg_pool(x, k=2, stride=2):
    s = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, 1, k, k), (1, 1, stride, stride), "VALID")
    return s / (k * k)


def global_avg_pool(x):
    return jnp.mean(x, axis=(2, 3))


# ----------------------------------------------------------------------------
# Norms for LM side
# ----------------------------------------------------------------------------

def rmsnorm_init(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(p, x, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm_apply(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)
