"""Mesh construction for the production topology (TPU v5e target).

Importing this module never touches jax device state; meshes are built
lazily inside functions.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules in
    ``dist.sharding`` are annotations the compiler propagates, which the
    explicit-sharding default of newer jax rejects on reshapes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU tests (requires the host-device count to allow it)."""
    return _mesh((data, model), ("data", "model"))


def make_cli_mesh(spec: str | None = None):
    """Mesh from a "data,model" CLI spec; default is all devices data-parallel.

    Shared by the train/serve launchers so both planes agree on axis names.
    """
    if spec:
        try:
            d, m = (int(x) for x in spec.split(","))
        except ValueError:
            raise SystemExit(
                f"--mesh expects 'data,model' (e.g. '4,2'), got {spec!r}")
    else:
        d, m = len(jax.devices()), 1
    return _mesh((d, m), ("data", "model"))
