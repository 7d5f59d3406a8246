"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS before any jax initialization and only then builds meshes.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh"]


def _auto_mesh(shape, axes, devices=None):
    # The sharding rules here are GSPMD hints (NamedSharding placements and
    # with_sharding_constraint), written for Auto axes; jax.make_mesh
    # defaults to Explicit axes, under which the model's gathers are refused.
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod (data=16, model=16)=256 chips; multi-pod adds pod=2."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(devices=None):
    """``devices`` (default: all of them) as a 1-D data mesh."""
    devices = list(jax.devices() if devices is None else devices)
    return _auto_mesh((len(devices), 1), ("data", "model"), devices)
