"""Production meshes.  Functions, not constants — importing this module
never touches jax device state (the dry-run sets device-count flags first).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for {axes} mesh, have {len(devs)} — the "
            f"dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count")
    import numpy as np
    return jax.sharding.Mesh(
        np.asarray(devs[:n]).reshape(shape), axes,
        axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (fake or real) devices exist — used by
    tests and CPU examples, same axis names as production."""
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2)
