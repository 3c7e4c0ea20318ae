"""Production mesh builders.

Functions (not module-level constants) so importing never touches jax device
state.  Production target: TPU v5e pods, 256 chips each.

  single-pod:  (data=16, model=16)            = 256 chips
  multi-pod:   (pod=2, data=16, model=16)     = 512 chips
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    # Auto axes: the sharding rules are annotations XLA propagates, not
    # explicit per-op sharding types (jax.make_mesh defaults to Explicit).
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2) -> jax.sharding.Mesh:
    """Small host-device mesh for CPU integration tests."""
    return _mesh((data, model), ("data", "model"))


def dp_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


# v5e hardware constants for the roofline (per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW_PER_LINK = 50e9          # B/s  (~per link)
