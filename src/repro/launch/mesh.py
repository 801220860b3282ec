"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — jax locks the device count on first
backend initialisation, and only dryrun.py is allowed to set the 512-device
flag (in its first two lines, before any other import).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_smoke_mesh(n_devices: int | None = None):
    """``(1, n)`` mesh over however many devices exist — the sharded
    field's default mesh.  Its axes are ``Auto``: the field post-processes
    the ``shard_map`` outputs with plain indexing, which an ``Explicit``
    axis (``jax.make_mesh``'s default) refuses to resolve."""
    n = n_devices or len(jax.devices())
    auto = jax.sharding.AxisType.Auto
    return jax.make_mesh((1, n), ("data", "model"), axis_types=(auto, auto))


def chips_in(mesh) -> int:
    return int(mesh.devices.size)
