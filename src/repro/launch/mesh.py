"""Production meshes.  A FUNCTION (not a module-level constant) so importing
never touches jax device state."""
from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: GSPMD propagates
    shardings from the constraints the models place (``core.sharding``)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh for CPU tests (forced host devices)."""
    return make_mesh((n_data, n_model), ("data", "model"))
