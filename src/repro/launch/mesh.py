"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS first).  Every axis
is ``Auto``: the model's ``with_sharding_constraint`` calls and the GSPMD
parameter shardings expect the compiler to choose the collectives.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
               devices=None) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256-chip pod; multi_pod adds a 2-pod leading axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(n_devices: int | None = None) -> jax.sharding.Mesh:
    """(data=1, model=n) mesh over the first ``n_devices`` local devices
    (all of them by default)."""
    devices = jax.devices()[:n_devices]
    return _auto_mesh((1, len(devices)), ("data", "model"), devices)
