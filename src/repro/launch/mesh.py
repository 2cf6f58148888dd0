"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — required for the dry-run's
XLA_FLAGS ordering (launch/dryrun.py sets the 512-device flag before any
jax initialization).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """jax.make_mesh with every axis in Auto sharding mode."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod ("data","model"); 2 pods adds a pure-DP "pod"
    axis (cross-pod traffic = one gradient all-reduce per step, DCN-friendly).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Mesh over whatever devices exist — the elastic-scaling entry point:
    axis sizes are derived from the live device count at (re)launch, and
    every sharding rule is expressed against axis *names*, so any
    (pods, data, model) factorization lowers unchanged (DESIGN.md §5)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    return _make_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    """Axes carrying the global batch (pure DP axes + the FSDP axis)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
