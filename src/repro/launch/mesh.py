"""Production meshes (importing this module never touches device state)."""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh",
           "make_host_mesh"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips for the multi-pod pass."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """Whatever devices exist locally, as a (data, model=1) mesh."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def make_host_mesh(model: int = 2):
    """A (data, model) mesh over all local devices with a real tensor axis —
    the test-suite mesh for forced 8-device CPU runs."""
    n = len(jax.devices())
    if n % model:
        raise ValueError(f"{n} devices not divisible by model={model}")
    return make_mesh((n // model, model), ("data", "model"))
