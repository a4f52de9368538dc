"""Production meshes.  A FUNCTION, not a module-level constant, so importing
this module never touches jax device state.

Every axis is ``Auto``: the LM stack places parameters and activations by
sharding annotations and leaves the rest to GSPMD propagation, which
``jax.make_mesh``'s default ``Explicit`` axes (JAX >= 0.7) would refuse at
the first gather or einsum whose output sharding is ambiguous."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None, model: int = 2):
    """Small mesh over however many devices exist (CPU tests)."""
    n = n_devices or len(jax.devices())
    data = max(1, n // model)
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
