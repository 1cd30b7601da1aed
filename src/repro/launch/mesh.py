"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).

Topology: TPU v5e, 256 chips per pod arranged (16, 16); the multi-pod mesh
prepends a `pod` axis (DCN/superpod links).  At larger scale the same
function extends: pods×16×16 with `pod` as the pure-DP (or PP) axis —
DESIGN.md §6.
"""
from __future__ import annotations

import jax


def _auto(axes):
    """The model code leaves partitioning to the compiler (GSPMD), so its
    meshes use Auto axes; ``jax.make_mesh`` defaults to Explicit."""
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_host_mesh():
    """1-device mesh for smoke tests / local runs."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_auto("ab"))
