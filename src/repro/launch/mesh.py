"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds meshes.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def _make(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (elastic re-mesh ladder, tests)."""
    return _make(shape, axes)


def make_data_mesh(devices: int = 0, axis: str = "data") -> Optional[Mesh]:
    """1-D serve mesh over the first ``devices`` local devices (0 = all).
    Returns None when that is one device — callers treat None as the
    single-device fast path (Forest.set_mesh(None)). Asking for more devices
    than are present raises: a sharded deployment must not silently run on
    fewer chips than it was configured for.

    Host-simulated multi-device testing: set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE the first
    jax import, then ``make_data_mesh(N)``."""
    import numpy as np

    avail = jax.devices()
    if devices > len(avail):
        raise ValueError(
            f"make_data_mesh: {devices} devices requested, "
            f"{len(avail)} present ({avail[0].platform})")
    n = len(avail) if devices <= 0 else devices
    if n <= 1:
        return None
    return Mesh(np.asarray(avail[:n]), (axis,))


def make_host_mesh(model_parallel: int = 1) -> Optional[Mesh]:
    """Largest mesh expressible on the actually-available devices."""
    n = len(jax.devices())
    if n == 1:
        return None
    data = n // model_parallel
    return make_mesh((data, model_parallel), ("data", "model"))
