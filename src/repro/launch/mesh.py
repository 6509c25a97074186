"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state.  Every mesh axis is ``AxisType.Auto``: the models
rely on GSPMD propagation.
"""

from __future__ import annotations

import math

import jax


def _mesh(shape, axes) -> jax.sharding.Mesh:
    # Pin the device subset explicitly so a 512-device dry-run host can
    # build a 256-chip pod.
    n = math.prod(shape)
    devices = jax.devices()[:n]
    from jax.experimental import mesh_utils
    dmesh = mesh_utils.create_device_mesh(shape, devices=devices)
    return jax.sharding.Mesh(
        dmesh, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over whatever devices exist (CPU tests, one-chip serving)."""
    return _mesh((data, model), ("data", "model"))
