"""Production mesh construction.

A FUNCTION (not module-level state) so importing never touches jax device
state; the dry-run sets XLA_FLAGS before any jax import to fake 512 host
devices (see dryrun.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's target mesh: (16, 16) data x model, or
    (2, 16, 16) pod x data x model with ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
