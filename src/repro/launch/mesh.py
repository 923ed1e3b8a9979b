"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not module state) so importing this
module never touches jax device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; everything else (tests, benches) sees the real single CPU device.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devs = jax.devices()
    if len(devs) >= need > 1:
        import numpy as np

        grid = np.array(devs[:need]).reshape(shape)
        return jax.sharding.Mesh(grid, axes)
    return _make_mesh(shape, axes)


def make_smoke_mesh():
    """1-device mesh with the same logical axes (CPU tests)."""
    return _make_mesh((1, 1), ("data", "model"))


def batch_axes_of(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_of(mesh) -> str:
    return "model"


# --- TPU v5e hardware constants (roofline denominators) ---
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
