"""Production mesh construction.

v5e target: 16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.
A FUNCTION (not a module constant) so importing never touches jax device
state — the dry-run sets XLA_FLAGS before any jax initialisation.
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_host_mesh():
    """1x1 mesh on the single real CPU device (tests / examples)."""
    auto = (jax.sharding.AxisType.Auto,) * 2
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=auto)


def make_decode_mesh(data: int, model: int):
    """(data, model) decode mesh over the first data*model local devices.

    Uses the plain ``jax.sharding.Mesh`` constructor, so it works on any
    backend, including a CPU forced to N devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    n = data * model
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"mesh {data}x{model} needs {n} devices but only "
            f"{len(devices)} are visible (on CPU, set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n})")
    grid = np.asarray(devices[:n]).reshape(data, model)
    return jax.sharding.Mesh(grid, ("data", "model"))
