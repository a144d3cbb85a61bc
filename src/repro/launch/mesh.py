"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and then calls it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (elastic rescale path uses shrunk shapes).

    Axes are ``Auto``: the step builders place activations with
    ``with_sharding_constraint``, which refuses ``Explicit`` axes (the
    ``jax.make_mesh`` default in JAX 0.9).
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))

