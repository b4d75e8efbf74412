"""Production mesh builders.

single pod : (16, 16)    axes (data, model)   = 256 chips (one v5e pod)
multi pod  : (2, 16, 16) axes (pod, data, model) = 512 chips

Functions, not module constants: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh_auto(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axis types (bare ``make_mesh`` makes them
    Explicit, which the sharding-in-types rules then enforce), over
    ``devices`` (default: all of them)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (smoke tests / examples)."""
    n = len(jax.devices())
    mp = model_parallel if n % max(model_parallel, 1) == 0 else 1
    return make_mesh_auto((n // mp, mp), ("data", "model"))
