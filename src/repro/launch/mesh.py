"""Production meshes.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run must set
``--xla_force_host_platform_device_count`` BEFORE any jax initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes are ``Auto``: shardings come from the ``NamedSharding``s and
    ``with mesh:`` contexts the dry-run sets, not from explicit-axis
    typing (``jax.make_mesh``'s default)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh():
    """1x1 mesh for CPU smoke tests / benches (1 visible device)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
