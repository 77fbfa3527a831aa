"""Production meshes. Functions, not module-level constants — importing this
module must never touch jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count before first jax init)."""

from __future__ import annotations

from collections.abc import Sequence

import jax
from jax.sharding import AxisType


def auto_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with ``Auto`` axes: shardings propagate as in GSPMD.

    JAX's default is ``Explicit`` axes, under which gathers such as the
    embedding lookup demand an explicit ``out_sharding``."""
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 0, n_model: int = 1):
    """Small mesh over whatever devices exist (tests / laptop runs)."""
    n = jax.device_count()
    if n_data <= 0:
        n_data = max(1, n // n_model)
    return auto_mesh((n_data, n_model), ("data", "model"))
