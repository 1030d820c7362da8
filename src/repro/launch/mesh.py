"""Production mesh construction (spec §MULTI-POD DRY-RUN).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state; callers (dryrun/train/serve) control
``XLA_FLAGS`` before first jax init.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (stated, not defaulted)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh over whatever local devices exist (tests)."""
    return make_mesh(shape, axes)


def data_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def mesh_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
