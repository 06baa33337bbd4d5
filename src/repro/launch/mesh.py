"""Device meshes (brief: MULTI-POD DRY-RUN §1).

Functions, not module-level constants, so importing this module never
touches jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``.

    ``distributed.hints.constrain`` places activations with
    ``with_sharding_constraint``, which accepts only Auto axes; JAX's own
    default is Explicit."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_device_mesh(data: int = 1, model: int = 1, devices=None):
    """(data, model) mesh over ``devices`` (default ``jax.devices()``),
    each axis clipped to what the devices allow."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    data = min(data, n)
    model = max(1, min(model, n // data))
    return auto_mesh((data, model), ("data", "model"),
                     devices=devices[:data * model])
