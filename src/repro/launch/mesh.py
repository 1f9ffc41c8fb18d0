"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips ('data','model');
multi-pod: 2x16x16 = 512 chips ('pod','data','model') — the 'pod' axis is
pure data parallelism across DCN.
"""

from __future__ import annotations

import math

import jax


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis Auto-sharded."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(shape: tuple[int, ...] = (1, 1),
                   axes: tuple[str, ...] = ("data", "model")):
    """Small mesh over the devices of this host (tests / local runs).
    Raises when ``shape`` asks for more devices than exist."""
    n, avail = math.prod(shape), len(jax.devices())
    if n > avail:
        raise ValueError(f"mesh {shape} needs {n} devices; "
                         f"this runtime has {avail}")
    return _mesh(shape, axes)
