"""Production mesh builders (DESIGN.md §7, system-prompt contract).

Functions — NOT module-level constants — so importing this module never
touches jax device state. The dry-run process sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import to make 512 placeholder host devices available.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (v5e); multi_pod adds a 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_tier_mesh(tier: str):
    """End-edge-cloud tiers as submesh sizes (DESIGN.md §2): the
    orchestrator's 'device' is a single chip, 'edge' an 8-chip slice,
    'cloud' the full single-pod mesh. Used by launch/serve.py; on the
    CPU container these all collapse to available devices."""
    n = len(jax.devices())
    shapes = {"S": (1, 1), "E": (1, min(8, n)), "C": (1, n)}
    shape = shapes[tier]
    return jax.make_mesh(shape, ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

