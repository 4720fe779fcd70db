"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this
module never touches jax device state — required because the dry-run
must set XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ('data', 'model').
    Multi-pod: 2x16x16 = 512 chips ('pod', 'data', 'model') — the pod
    axis is an outer data-parallel axis crossing the inter-pod (DCN/ICI)
    boundary."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh (tests use small host-device meshes, e.g. (4, 2))."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
