"""Shared small utilities: PRNG plumbing, pytree helpers, shape math."""
from __future__ import annotations

import math
from typing import Any, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jex_core

PyTree = Any


def key_iter(key: jax.Array):
    """Infinite iterator of fresh PRNG keys."""
    while True:
        key, sub = jax.random.split(key)
        yield sub


def param_count(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def param_bytes(tree: PyTree) -> int:
    return sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(tree)
    )


def tree_cast(tree: PyTree, dtype) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree,
    )


def tree_zeros_like(tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_scale(tree: PyTree, s) -> PyTree:
    return jax.tree_util.tree_map(lambda x: x * s, tree)


def global_norm(tree: PyTree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def check_finite(tree: PyTree) -> jax.Array:
    """True iff every leaf is finite."""
    leaves = [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(tree)
              if jnp.issubdtype(x.dtype, jnp.floating)]
    return jnp.all(jnp.stack(leaves)) if leaves else jnp.asarray(True)


def pallas_eqns(jaxpr) -> list:
    """Every pallas_call equation in a jaxpr, in trace order, recursing
    through sub-jaxprs. THE launch counter — the structural contract
    tests (tests/test_mask_pack.py) and the kernel benchmarks
    (benchmarks/kernel_bench.py) must count the same way, so both use
    this."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
            continue                     # kernel bodies never nest launches
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    out.extend(pallas_eqns(sub.jaxpr))
                elif isinstance(sub, jex_core.Jaxpr):
                    out.extend(pallas_eqns(sub))
    return out


def pallas_grids(jaxpr) -> list[tuple[int, ...]]:
    """Grid shape of every pallas_call in a jaxpr, in trace order."""
    return [tuple(e.params["grid_mapping"].grid) for e in pallas_eqns(jaxpr)]


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} PiB"


def human_flops(n: float) -> str:
    for unit in ("FLOP", "KFLOP", "MFLOP", "GFLOP", "TFLOP", "PFLOP"):
        if abs(n) < 1000.0:
            return f"{n:.2f} {unit}"
        n /= 1000.0
    return f"{n:.2f} EFLOP"
