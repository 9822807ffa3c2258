"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state.  The dry-run's ``main`` pins its process
to 512 virtual CPU devices before any backend starts; tests and benches
see the real devices.
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips) mesh.

    Axes: ("data", "model") / ("pod", "data", "model").  DP runs over
    pod+data, TP/EP over model, context-parallel decode over data.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(tp: int = 2, dp: int = 1):
    """Small mesh for CPU tests (requires host-platform device override)."""
    n = tp * dp
    devs = np.array(jax.devices()[:n]).reshape(dp, tp)
    return jax.sharding.Mesh(devs, ("data", "model"))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod folds into DP)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
